"""The benchmark's calls into the system under test, shared by the drivers:
build the app's runtime, prove where it runs, and read its own counters."""


def app_text(cell: dict, source: str = "", sink: str = "") -> str:
    """The configuration's annotations and its SiddhiQL file, with the
    driver's @source/@sink annotations (empty in process) filled in."""
    body = cell["app_text"].replace("{source}", source).replace(
        "{sink}", sink)
    return "\n".join(cell["config"]["annotations"]) + "\n" + body


def tape_of(cell: dict, seed: int):
    """The run's seeded stream: the configuration's generator and
    parameters at the traffic mix's batch size."""
    from benchmark import manifest
    cfg, tr = cell["config"], cell["traffic"]
    params = {**cfg["tape_params"], "batch": int(tr["batch"])}
    skew = tr.get("skew")
    if skew and params["keys"] >= skew["only_if_keys_at_least"]:
        params["skew"] = skew
    if not cfg["stateful"] and tr.get("ring_batches"):
        params["ring"] = int(tr["ring_batches"])
    return manifest.module("tapes", cfg["tape"]).Tape(params, seed)


def check_placement(rt, cell: dict, platform: str) -> dict:
    """The app runs where the configuration says, or the run is void:
    EXPLAIN path/kind/family, no demotion, an empty ErrorStore, and on a
    mesh every state leaf sharded over the stated number of devices."""
    want = cell["config"]["expect"]
    ex = rt.explain()
    if not ex["queries"] or ex["demotions"]:
        raise RuntimeError(f"placement: queries {list(ex['queries'])}, "
                           f"demotions {ex['demotions']}")
    for q, ent in ex["queries"].items():
        got = (ent["path"], ent["kind"], ent.get("family"))
        if got != (want["path"], want["kind"], want["family"]):
            raise RuntimeError(f"placement: query {q!r} runs {got}, the "
                               f"configuration states {want}: {ent}")
    if len(rt.error_store):
        raise RuntimeError(f"ErrorStore holds {len(rt.error_store)} captures")
    shards = {}
    for plan in rt._plans:          # no public view of a plan's state yet
        state = getattr(plan, "state", None)
        if not isinstance(state, dict):
            continue
        for key, leaf in state.items():
            devs = leaf.devices()
            if any(d.platform != platform for d in devs):
                raise RuntimeError(f"{plan.name}.state[{key!r}] lives on "
                                   f"{devs}, expected {platform}")
            shards[f"{plan.name}.{key}"] = len(leaf.sharding.device_set)
    if want["sharded_over"]:
        wrong = {k: n for k, n in shards.items()
                 if n != want["sharded_over"]}
        if wrong or not shards:
            raise RuntimeError(f"state leaves not sharded over "
                               f"{want['sharded_over']} devices: "
                               f"{wrong or 'no state'}")
    elif any(n != 1 for n in shards.values()):
        raise RuntimeError(f"unexpected sharding: {shards}")
    return {"queries": {q: (e["path"], e["kind"], e.get("family"))
                        for q, e in ex["queries"].items()},
            "state_leaves": shards}


def counters(rt) -> dict:
    """The engine's own stage seconds and transfer bytes so far."""
    st = rt.statistics()
    out = {"stages": {k: v["seconds"] for k, v in st.get("stages", {}).items()},
           "h2d_bytes": 0, "d2h_bytes": 0, "lanes": None}
    for gauges in st.get("device", {}).values():
        if gauges.get("lanes_last_dispatch"):
            out["lanes"] = int(gauges["lanes_last_dispatch"])
    for name, plan in st.get("profile", {}).get("plans", {}).items():
        for k, v in plan.get("bytes", {}).items():
            out[f"{k}_bytes"] += v
    return out


def delta(after: dict, before: dict) -> dict:
    return {"stages": {k: v - before["stages"].get(k, 0.0)
                       for k, v in after["stages"].items()},
            "h2d_bytes": after["h2d_bytes"] - before["h2d_bytes"],
            "d2h_bytes": after["d2h_bytes"] - before["d2h_bytes"],
            "lanes": after["lanes"]}
