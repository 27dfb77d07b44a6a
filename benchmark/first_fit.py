"""A frame's first fit, read from the program's own span ring.

`run.py` starts the profiler after the warm-up fit, so nothing of set-up is
on the profiler's planes and `fit_spans.py` cannot see it. But
`h2o3_tpu/runtime/tracing.py` records every span into the process's ring
whether or not a profiler runs, with parent ids, and after the window the
warm-up's `train` tree is still there (a traced window is some hundreds of
spans of a ring of 4,096). The process's FIRST `train` root is the warm-up
fit; its descendants by `parent_id`, transitively, are its share of
`setup_s`: the design build's stages, the compile requests
(`xla.compile` / `xla.cache_load`, wherever the first call of a program was
made, the tree fit's warm-up thread included) and the trace seconds tallied
on the spans (`xla_trace_s`). The readers run in the traced call, where
set-up runs exactly as in the untraced one and before the profiler starts.

Every function answers None where the ring cannot answer: a program from
before `tracing.dropped()` (a parent commit), a ring that has evicted
something, no `train` root, or fewer `train` roots than the window's fits
and the warm-up."""

from __future__ import annotations


def ring():
    """(spans, dropped) of the program's ring, or None where the program
    cannot say that nothing was evicted."""
    from h2o3_tpu.runtime import tracing

    dropped = getattr(tracing, "dropped", None)
    if dropped is None:
        return None
    return tracing.spans(), dropped()


def tree(ctx):
    """(root, descendants) of the first `train` root, or None."""
    got = ring()
    if got is None:
        return None
    spans, dropped = got
    roots = [s for s in spans if s["name"] == "train"
             and s["parent_id"] is None]
    if dropped or not roots or len(roots) < ctx["fits"] + 1:
        return None
    root = min(roots, key=lambda s: s["ts"])
    children = {}
    for s in spans:
        children.setdefault(s["parent_id"], []).append(s)
    under, todo = [], [root]
    while todo:
        kids = children.get(todo.pop()["span_id"], [])
        under += kids
        todo += kids
    return root, under


def seconds(ctx, *names):
    """Summed duration of the first fit's spans called one of `names`, or
    None where it has none."""
    got = tree(ctx)
    found = [s for s in got[1] if s["name"] in names] if got else []
    if not found:
        return None
    return sum(s["duration_s"] for s in found)


def pipeline(ctx):
    """`tree` of a program that puts its compile requests on the span tree
    (spans of kind `xla`: a first fit compiles or loads at least one
    program), else None."""
    got = tree(ctx)
    if not got or not any(s["kind"] == "xla" for s in got[1]):
        return None
    return got


def requests(ctx, name: str):
    """The first fit's compile requests called `name` (`xla.compile` or
    `xla.cache_load`): a list, empty where every request was of the other
    kind, or None (`pipeline`)."""
    got = pipeline(ctx)
    return None if got is None else [s for s in got[1] if s["name"] == name]


def tallied(ctx, attr: str):
    """The sum of attr `attr` over the first fit's spans, or None
    (`pipeline`)."""
    got = pipeline(ctx)
    if got is None:
        return None
    return sum(s["attrs"].get(attr, 0.0) for s in (got[0], *got[1]))
