"""Shares of the traced window from the device trace: busy is the union of
the intervals in which an operation ran, on the busiest device; idle is the
rest.  No device plane in the trace: nothing to read."""


def read(spec: dict, obs: dict):
    t = obs.get("trace")
    if not t or not t.get("devices") or not t["window_s"]:
        return None
    busy = t["devices"][t["busiest"]]["busy_s"]
    share = 100.0 * busy / t["window_s"]
    return share if spec["quantity"] == "busy_share" else 100.0 - share
