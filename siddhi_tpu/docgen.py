"""Extension documentation generator.

Reference: modules/siddhi-doc-gen (Maven mojo generating mkdocs pages
from @Extension metadata — MarkdownDocumentationGenerationMojo renders
name/namespace/description/@Parameter/@Example per extension).  Here
the extension surface is the registries plus the built-in metadata
table (`siddhi_tpu.extension`), so docs generate directly from them:
every built-in window and aggregator gets a full section with
parameters, return contract, and examples; user extensions registered
with `meta=ExtensionMeta(...)` render the same way, others fall back
to a docstring line.

Run:  python -m siddhi_tpu.docgen [out.md]
"""
from __future__ import annotations

import inspect
from typing import Optional

from .extension import ExtensionMeta, all_meta, meta_for


def _meta_section(m: ExtensionMeta, level: str = "###") -> list:
    name = f"{m.namespace}:{m.name}" if m.namespace else m.name
    lines = [f"{level} `{name}`", "", m.description, ""]
    if m.parameters:
        lines += ["| parameter | types | description | optional | default |",
                  "|---|---|---|---|---|"]
        for p in m.parameters:
            lines.append(
                f"| `{p.name}` | {', '.join(str(t) for t in p.type)} | "
                f"{p.description} | {'yes' if p.optional else 'no'} | "
                f"{'' if p.default is None else p.default} |")
        lines.append("")
    if m.returns:
        lines += [f"**Returns**: {m.returns}", ""]
    for e in m.examples:
        lines += ["```siddhi", e.syntax, "```", "", e.description, ""]
    return lines


def _registry_rows(registry: dict, kind: str) -> list:
    """(name, meta-or-docline) rows for a user-extension registry."""
    out = []
    for key in sorted(registry, key=str):
        obj = registry[key]
        if isinstance(key, str):
            ns, name = "", key
        else:
            ns, name = (key[0] or ""), key[1]
        m = meta_for(kind, name, ns)
        if m is not None:
            out.append((name, m))
            continue
        doc = ""
        if inspect.isclass(obj) or inspect.isfunction(obj):
            doc = (inspect.getdoc(obj) or "").split("\n")[0]
        disp = f"{ns}:{name}" if ns else name
        out.append((disp, doc))
    return out


def generate_markdown() -> str:
    """One markdown document covering every extension point."""
    from .core.expr import SCALAR_FUNCTIONS
    from .core.io import SINK_MAPPERS, SINK_TYPES, SOURCE_MAPPERS, SOURCE_TYPES
    from .core.record_table import STORE_TYPES
    from .core.telemetry import REPORTERS
    from .interp.expr import PY_FUNCTIONS
    from .interp.engine import STREAM_FUNCTIONS, WINDOW_TYPES
    from .interp.aggregators import AGGREGATOR_CLASSES

    lines = ["# siddhi-tpu extension reference", "",
             "Generated from the live extension registries and built-in "
             "metadata (`python -m siddhi_tpu.docgen`).", ""]

    # windows + aggregators: built-ins and meta-registered extensions
    # render full sections; meta-less registered extensions fall back to
    # a docstring table row
    lines += ["## Windows (`#window.<name>(...)`)", ""]
    for m in all_meta("window"):
        lines += _meta_section(m)
    plain = [(n, d) for n, d in _registry_rows(WINDOW_TYPES, "window")
             if not isinstance(d, ExtensionMeta)]
    lines += _plain_table(plain)
    lines += ["## Aggregators (selector functions)", ""]
    for m in all_meta("aggregator"):
        lines += _meta_section(m)
    plain = [(n, d) for n, d in _registry_rows(AGGREGATOR_CLASSES,
                                               "aggregator")
             if not isinstance(d, ExtensionMeta)]
    lines += _plain_table(plain)

    sections = [
        ("Scalar functions (device expression compiler)", SCALAR_FUNCTIONS,
         "function"),
        ("Scalar functions (host interpreter)", PY_FUNCTIONS, "function"),
        ("Stream functions (`#<ns>:<name>(...)`)", STREAM_FUNCTIONS,
         "stream-function"),
        ("Source types (`@source(type=...)`)", SOURCE_TYPES, "source"),
        ("Sink types (`@sink(type=...)`)", SINK_TYPES, "sink"),
        ("Source mappers (`@map(type=...)`)", SOURCE_MAPPERS,
         "source-mapper"),
        ("Sink mappers (`@map(type=...)`)", SINK_MAPPERS, "sink-mapper"),
        ("Store types (`@store(type=...)`)", STORE_TYPES, "store"),
        ("Statistics reporters (`@app:statistics(reporter=...)`)",
         REPORTERS, "stats-reporter"),
    ]
    for title, registry, kind in sections:
        lines += [f"## {title}", ""]
        rows = _registry_rows(registry, kind)
        for _n, m in rows:
            if isinstance(m, ExtensionMeta):
                lines += _meta_section(m)
        lines += _plain_table(
            [(n, d) for n, d in rows if not isinstance(d, ExtensionMeta)])
    return "\n".join(lines)


def _plain_table(rows: list) -> list:
    if not rows:
        return []
    out = ["| name | description |", "|---|---|"]
    for name, doc in rows:
        out.append(f"| `{name}` | {doc.replace('|', '/')} |")
    out.append("")
    return out


def main(out: Optional[str] = None) -> None:
    md = generate_markdown()
    if out:
        with open(out, "w") as f:
            f.write(md)
        print(f"wrote {out} ({len(md.splitlines())} lines)")
    else:
        print(md)


if __name__ == "__main__":
    import sys
    main(sys.argv[1] if len(sys.argv) > 1 else None)
