"""tpu_hist — per-(node, feature, bin) gradient histograms.

Reference parity: this op IS the hot loop of the reference's tree engines:
`h2o-algos/src/main/java/hex/tree/DHistogram.java` (`updateHisto`: per-row
per-column accumulate of {count, Σy, Σy²}) driven by
`hex/tree/ScoreBuildHistogram2.java` (the MRTask whose `reduce()` adds
histogram arrays across nodes), and XGBoost's CUDA `gpu_hist` updater
(shipped as `libxgboost4j_gpu.so` in `h2o-ext-xgboost`).

On TPU, scatter-add (the GPU approach: atomics into shared-memory
histograms) is the enemy — the VPU has no atomics and XLA lowers scatter to
serialized updates. Three kernels; `resolve_method` is the one rule that
picks among them (``pallas_factored`` on a TPU with ``segment`` as its
VMEM fallback, ``segment`` on a CPU, ``onehot`` on other accelerators):

* ``onehot``: encode (node,bin) as a one-hot matrix and reduce with a
  matmul — rides the MXU. hist[c, l*B+b] = Σ_rows vals[c,row] ·
  onehot[row, l*B+b], scanned over features. O(N·L·B) FLOPs per feature but
  systolic-array FLOPs are nearly free at these sizes.
* ``segment``: `jax.ops.segment_sum` with ids = node·B + bin (XLA sorted
  scatter). The kernel of very large L·B (the VMEM fallback) and of
  CPU fits.
* ``pallas_factored``: the fused VMEM kernel in
  `hist_pallas.py`. It builds its bin one-hot a feature at a time on a bin
  axis padded to the sublane tile (`hist_pallas.bins_padded`: 21 → 24, so
  no tile of the one-hot straddles two features; the padded bins are exact
  zeros and are sliced off before the result leaves the op — the fit plan's
  `bins_padded` says which width ran). Its code operand is the
  feature-major float32 (F, N) array `feature_major` names. Where a fit's
  plan runs this kernel on one device, ONE program a fit
  (`build_code_operand`) widens the resident codes into it, already padded
  to the kernel's blocks, and every tree program takes it as an argument
  (`code_operand_form` is the rule, the fit plan's `code_operand` says
  which form a fit ran). Elsewhere (the
  blocked and mesh lanes, `run_block_kernel`) the program widens packed
  input in-graph, once per program execution. Either way the RESIDENT
  matrix — what the dataset cache holds across fits and what the H2D
  upload moves — stays packed. True in-kernel sub-byte
  decode is blocked by Mosaic's (32, 128) int8 tile granularity at the
  kernel's 8-feature block shape (see docs/perf.md).

The cross-host combine (ScoreBuildHistogram2.reduce / Rabit allreduce) is a
single `lax.psum` over the ``hosts`` mesh axis, applied by the caller inside
`shard_map` — see `h2o3_tpu/models/tree.py`.

Sharded determinism (ISSUE 12): with ``n_shard_blocks`` > 0 the rows are
accumulated as per-block PARTIAL histograms (each block a contiguous,
equal-sized row range) that are gathered into global block order
(`lax.all_gather`, device-major == row order) and folded LEFT-TO-RIGHT —
a fixed reduction tree independent of how many devices the blocks live
on. An N-device fit and a 1-device fit configured with the same total
block count therefore produce BIT-IDENTICAL histograms: each block
partial is the same sequential in-order f32 fold over the same rows,
and the cross-block fold order is pinned by the expression tree. This is
what makes "8-device fit == 1-device fused fit" a bit-stability pin
rather than an allclose hope.

Kernel-selection observability (ISSUE 7): every dispatch records the chosen
method (and the VMEM-pressure pallas→segment fallbacks) into the central
metrics registry, and the tree driver records a per-fit level plan via
``record_fit_plan`` — surfaced at ``GET /3/Profiler`` under ``tree`` so
"which kernel actually ran, at which row_chunk and padded bin width" is
never guesswork.
"""

from __future__ import annotations

import functools
import os
import threading
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp

from . import packing

# what `method=` / `hist_method=` / H2O3_HIST_METHOD may name
METHODS = ("auto", "onehot", "segment", "pallas_factored")


def _factored_row_chunk(n_nodes: int, nbins: int) -> int:
    """Largest row chunk whose co-resident VMEM buffers fit the kernel's
    stated `hist_pallas.VMEM_LIMIT_BYTES` (16 MiB): the (3L,R) f32 scratch
    and (8·Bp,R) bf16 bin one-hot each ≤ half of it AND scratch + one-hot +
    the revisited (3L,8·Bp) f32 output block within it together, `Bp` the
    bin axis as the kernel pads it (`hist_pallas.bins_padded`). Held to the
    v5e compiler ahead of time (1M×28, B∈{16,21,33,64,256,1024}, every L
    up to the fallback): each chunk this picks compiles under the stated
    limit, and where the scratch term binds (L ≥ 128 at B ≤ 64) the next
    chunk up is refused ("ran out of memory in memory space vmem") — the
    scratch bound is the chip's; the one-hot bound is conservative (the next
    chunk up still compiles at B ≥ 256). Returns <512 when no chunk fits
    (caller falls back to the XLA segment path — recorded, see
    `resolve_method`)."""
    from .hist_pallas import VMEM_LIMIT_BYTES as limit, bins_padded

    bp = bins_padded(nbins)
    out_bytes = 3 * n_nodes * 8 * bp * 4
    rc = 8192
    while rc >= 512:
        scratch = 3 * n_nodes * rc * 4
        onehot = 8 * bp * rc * 2
        if scratch <= limit // 2 and onehot <= limit // 2 \
                and scratch + onehot + out_bytes <= limit:
            break
        rc //= 2
    return rc


# -- kernel-selection observability ----------------------------------------

_SEL_LOCK = threading.Lock()
_SEL_REG: dict = {}
_FIT_PLANS: "deque" = deque(maxlen=16)


def _sel_registry() -> dict:
    """Memoized registry families for kernel-selection counters (same
    memoization stance as runtime/phases._xla_counters)."""
    if not _SEL_REG:
        from ..runtime import metrics_registry as _reg

        _SEL_REG["dispatch"] = _reg.counter(
            "h2o3_tree_hist_dispatch",
            "histogram kernel dispatches by resolved method (trace-time)",
            labelnames=("method",))
        _SEL_REG["vmem_fallbacks"] = _reg.counter(
            "h2o3_tree_hist_vmem_fallbacks",
            "fit-plan levels (per fit, per level) whose pallas_factored "
            "selection fell back to the segment path because no VMEM row "
            "chunk >= 512 fits")
        _SEL_REG["partition_read"] = _reg.counter(
            "h2o3_tree_partition_read",
            "partition-step reads of a row's split-feature code by kind "
            "(trace-time): select = dense one-hot over the feature axis, "
            "gather = per-row gather (frames wider than the one-hot limit)",
            labelnames=("read",))
        _SEL_REG["code_operand"] = _reg.counter(
            "h2o3_tree_code_operand",
            "tree fits by where the histogram kernel's code operand is "
            "built: fit = one program a fit, handed to the tree programs; "
            "program = widened inside every tree program",
            labelnames=("built",))
    return _SEL_REG


def resolve_method(n_nodes: int, nbins: int, method: str = "auto",
                   platform: Optional[str] = None) -> dict:
    """The ONE auto-dispatch rule, shared by `build_histograms` and the
    driver's per-fit plan recording so the observed plan cannot diverge
    from what actually runs. Returns
    ``{"method", "row_chunk", "fallback"}`` — `row_chunk` is the pallas
    grid chunk (None off the pallas path), `fallback` names why a
    requested kernel was substituted (today: "vmem" for the
    `_factored_row_chunk` < 512 pressure fallback). A name that is not one
    of `METHODS` is refused here, before any kernel is traced."""
    if method == "auto":
        method = os.environ.get("H2O3_HIST_METHOD", "auto")
    if method not in METHODS:
        raise ValueError(f"unknown histogram method {method!r}: "
                         f"valid methods are {', '.join(METHODS)}")
    if platform is None:
        platform = jax.default_backend()
    if method == "auto":
        if platform == "cpu":
            method = "segment"
        elif platform == "tpu":
            # the factored pallas kernel, unconditionally: a Pallas TPU
            # module that fails to import is an error at the kernel's
            # import (`hist_pallas`), never a silent `onehot`
            method = "pallas_factored"
        else:
            method = "onehot"  # non-TPU accelerators: Mosaic won't lower
    row_chunk = None
    fallback = None
    if method == "pallas_factored":
        rc = _factored_row_chunk(n_nodes, nbins)
        if rc < 512:
            # scratch would not fit VMEM at any useful chunk. Deep levels
            # (L·B ≳ 20k) are where XLA's sorted-scatter wins: measured on
            # the real chip (50k×12, B=21) segment is 25–78 ms flat for
            # L=4k..64k vs 64–700 ms for the one-hot matmul paths
            method, fallback = "segment", "vmem"
        else:
            row_chunk = rc
    return {"method": method, "row_chunk": row_chunk, "fallback": fallback}


def _record_selection(sel: dict, vmem: bool = False) -> None:
    """Count a resolution. Each counter has ONE source so the numbers stay
    semantically consistent: `dispatch` counts trace-time kernel dispatches
    (`build_histograms` only — dispatches are rare by design), while
    `vmem_fallbacks` counts per-fit per-level plan entries
    (`record_fit_plan` only, `vmem=True`) — the 'once per fit' satellite
    contract, never double-counted by the trace that follows."""
    try:
        reg = _sel_registry()
        if vmem:
            if sel["fallback"] == "vmem":
                reg["vmem_fallbacks"].inc()
        else:
            reg["dispatch"].inc(1.0, sel["method"])
    except Exception:
        pass


def record_partition_read(read: str) -> None:
    """Count one traced partition-step read of a row's split-feature code
    (`tree._row_codes`): "select" or "gather". Trace-time, like `dispatch`
    — a warm fit re-traces nothing and counts nothing; the per-fit answer
    is the plan's `partition_read`."""
    try:
        _sel_registry()["partition_read"].inc(1.0, read)
    except Exception:
        pass


def code_operand_form(levels, nbins: int, hist_method: str,
                      shard_mode: str = "off",
                      platform: Optional[str] = None) -> dict:
    """The ONE rule for where the Pallas kernel's code operand is built:
    ``{"form", "row_chunk"}``. ``"fit"``: one program a fit builds it
    (`build_code_operand`) and the tree programs take it as an argument —
    a one-device fit (`shard_mode` "off") any of whose `levels` (the
    `(label, n_nodes)` of `tree.histogram_level_plan`) `resolve_method`
    gives to ``pallas_factored``, packed and full-width codes alike;
    `row_chunk` is then the largest row chunk of those levels, which every
    other level's chunk divides (powers of two). ``"program"``: the tree
    program widens what it needs itself — CPU fits (``segment``), the
    blocked and mesh lanes, the streamed blocks, lossguide growth (which
    passes no levels). Reads what the code can observe, no switch."""
    sels = [resolve_method(n_nodes, nbins, hist_method, platform=platform)
            for _, n_nodes in levels]
    chunks = [sel["row_chunk"] for sel in sels
              if sel["method"] == "pallas_factored"]
    if shard_mode != "off" or not chunks:
        return {"form": "program", "row_chunk": 0}
    return {"form": "fit", "row_chunk": max(chunks)}


def record_fit_plan(tag: str, levels, nbins: int, hist_method: str,
                    pack_bits: int = 0, platform: Optional[str] = None,
                    n_shards: int = 0, n_devices: int = 1,
                    partition_read: Optional[str] = None,
                    rank: Optional[dict] = None,
                    code_operand: str = "program",
                    operand_bytes: int = 0) -> dict:
    """Resolve + record the per-level kernel plan of one tree fit.

    `levels` is a sequence of (label, n_nodes) histogram passes the fit
    will run. Logs ONE warning per fit when any level hits the VMEM
    pressure fallback (the previously-silent `_factored_row_chunk` < 512
    path), counts every level's selection in the registry, and keeps the
    plan in a bounded ring surfaced at /3/Profiler. Beside a level's
    `row_chunk` stands `bins_padded`, the bin axis the Pallas kernel ran it
    on (`hist_pallas.bins_padded(nbins)`; None where another kernel ran the
    level). `partition_read` is
    how the fit's levels read a row's split-feature code
    (`tree.partition_read`; None for a fit without a level partition).
    `code_operand` is where the histogram kernel's code operand is built
    (`code_operand_form`: ``"fit"`` once a fit and handed to the tree
    programs, `operand_bytes` of it held for the fit; ``"program"`` inside
    every tree program, 0 bytes held).
    `rank` is a pairwise ranking objective's own plan
    (`models.xgboost._make_lambdarank`), kept under the key `rank`:
    `queries`, `group_max`, `group_mean`, `pairs` (the real ordered pairs),
    `pair_slots` (every slot the pass computes: over the size classes,
    padded queries x width², padding queries included) and `classes`, one
    dict of `width`, `queries`, `padded_queries`, `q_chunk` a class."""
    import time as _time

    plan_levels = []
    fellback = []
    for label, n_nodes in levels:
        sel = resolve_method(n_nodes, nbins, hist_method, platform=platform)
        _record_selection(sel, vmem=True)
        bp = None
        if sel["method"] == "pallas_factored":
            from .hist_pallas import bins_padded

            bp = bins_padded(nbins)
        plan_levels.append(dict(level=label, n_nodes=int(n_nodes), **sel,
                                bins_padded=bp))
        if sel["fallback"] == "vmem":
            fellback.append((label, int(n_nodes)))
    plan = dict(tag=tag, ts=_time.time(), nbins=int(nbins),
                hist_method=hist_method, pack_bits=int(pack_bits),
                n_shards=int(n_shards), n_devices=int(n_devices),
                partition_read=partition_read, code_operand=code_operand,
                operand_bytes=int(operand_bytes), levels=plan_levels)
    if rank is not None:
        plan["rank"] = dict(rank)
    try:
        _sel_registry()["code_operand"].inc(1.0, code_operand)
    except Exception:
        pass
    if fellback:
        from ..runtime.log import Log

        Log.warn(
            f"tree fit {tag}: histogram levels {fellback} exceed the VMEM "
            f"row-chunk floor — falling back to the segment kernel "
            "(counted in h2o3_tree_hist_vmem_fallbacks)")
    with _SEL_LOCK:
        _FIT_PLANS.append(plan)
    return plan


def attach_fit_stream(tag: str, stream: dict) -> None:
    """Attach a finished fit's out-of-core stream summary (blocks
    uploaded/evicted/reused, bytes streamed, bytes per tree, resident
    peak) to its recorded plan — the ISSUE 14 observability contract:
    the tree fold at /3/Profiler carries the streaming trajectory next
    to the kernel plan, so 'how many bytes did this fit move per tree'
    is a read, not a rerun."""
    with _SEL_LOCK:
        for plan in reversed(_FIT_PLANS):
            if plan["tag"] == tag:
                plan["stream"] = dict(stream)
                return


def attach_fit_skew(tag: str, skew: dict) -> None:
    """Attach a finished fit's collective-skew summary (mesh.lane_summary)
    to its recorded plan — the plan rings at /3/Profiler `tree` then carry
    per-fit {fences, skew_p50_ms, skew_max_ms, worst_lane} next to the
    kernel plan (ISSUE 13: per-fit skew summaries in the tree fold)."""
    with _SEL_LOCK:
        for plan in reversed(_FIT_PLANS):
            if plan["tag"] == tag:
                plan["collective_skew"] = dict(skew)
                return


def kernel_stats() -> dict:
    """Per-fit kernel plans + cumulative dispatch counters (the /3/Profiler
    `tree` fold). Pure counter read."""
    with _SEL_LOCK:
        plans = list(_FIT_PLANS)
    out = dict(plans=plans, dispatch={}, partition_read={}, code_operand={},
               vmem_fallbacks=0)
    try:
        reg = _sel_registry()
        for fam in ("dispatch", "partition_read", "code_operand"):
            out[fam] = {lv[0]: c.value()
                        for lv, c in reg[fam].children().items()}
        out["vmem_fallbacks"] = reg["vmem_fallbacks"].value()
    except Exception:
        pass
    return out


# -- kernels ----------------------------------------------------------------


def _hist_onehot(codes, node_id, vals, n_nodes: int, nbins: int):
    """MXU path. codes (N,F) int, node_id (N,) int, vals (3,N) f32.
    Returns (n_nodes, F, nbins, 3).

    Factored one-hot: the (node × channel)-weighted matrix (3L, N) is built
    ONCE per level and shared by every feature; each scan step only builds
    the (N, B) bin one-hot and runs one (3L,N)@(N,B) MXU matmul. This does
    N·B comparisons per feature instead of N·L·B — the VPU (comparison) work
    no longer scales with the node count."""
    N, F = codes.shape
    if 3 * n_nodes * N * 2 > (256 << 20):
        # deep levels: the shared (3L, N) weighted matrix would not fit —
        # fall back to the fused (node,bin) one-hot inside the scan
        LB = n_nodes * nbins
        base = node_id.astype(jnp.int32) * nbins
        iota = jnp.arange(LB, dtype=jnp.int32)

        def one_feature_fused(carry, code_f):
            cid = base + code_f.astype(jnp.int32)
            onehot = (cid[:, None] == iota[None, :]).astype(jnp.bfloat16)
            hist_f = jnp.dot(vals.astype(jnp.bfloat16), onehot,
                             preferred_element_type=jnp.float32)  # (3, LB)
            return carry, hist_f

        _, hists = jax.lax.scan(one_feature_fused, None, codes.T)
        return hists.reshape(F, 3, n_nodes, nbins).transpose(2, 0, 3, 1)

    node_oh = (node_id[:, None].astype(jnp.int32)
               == jnp.arange(n_nodes, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    weighted = vals.astype(jnp.bfloat16)[:, :, None] * node_oh[None, :, :]  # (3,N,L)
    weighted = weighted.transpose(0, 2, 1).reshape(3 * n_nodes, N)          # (3L,N)
    iota_b = jnp.arange(nbins, dtype=jnp.int32)

    def one_feature(carry, code_f):
        bin_oh = (code_f[:, None].astype(jnp.int32) == iota_b[None, :]).astype(jnp.bfloat16)
        hist_f = jnp.dot(weighted, bin_oh, preferred_element_type=jnp.float32)  # (3L,B)
        return carry, hist_f

    _, hists = jax.lax.scan(one_feature, None, codes.T)   # (F, 3L, B)
    return hists.reshape(F, 3, n_nodes, nbins).transpose(2, 0, 3, 1)


def _hist_segment(codes, node_id, vals, n_nodes: int, nbins: int):
    """Sorted-scatter path. Returns (n_nodes, F, nbins, 3)."""
    N, F = codes.shape
    base = node_id.astype(jnp.int32) * nbins

    def one_feature(carry, code_f):
        ids = base + code_f.astype(jnp.int32)
        hist_f = jax.ops.segment_sum(vals.T, ids, num_segments=n_nodes * nbins)  # (LB,3)
        return carry, hist_f

    _, hists = jax.lax.scan(one_feature, None, codes.T)   # (F, LB, 3)
    return hists.reshape(F, n_nodes, nbins, 3).transpose(1, 0, 2, 3)


def run_block_kernel(method: str, codes, node_id, vals, n_nodes: int,
                     nbins: int, pack_bits: int = 0,
                     row_chunk: "Optional[int]" = None):
    """One resolved kernel over one contiguous row block — the public
    entry the streamed out-of-core driver jits per block. Identical to
    each per-block partial of the blocked in-core reduction
    (`build_histograms` with ``n_shard_blocks``), which is what makes a
    streamed fit bit-identical to the in-core blocks fit."""
    return _run_kernel({"method": method, "row_chunk": row_chunk,
                        "fallback": None},
                       codes, node_id, vals, n_nodes, nbins, pack_bits)


def ordered_axis_fold(parts: jax.Array, axis_name: Optional[str],
                      timing_tag: Optional[str] = None) -> jax.Array:
    """Deterministic sum of per-block partials: gather the (local_blocks,
    ...) stack into GLOBAL block order (`all_gather` is device-major, which
    matches row order for contiguous row sharding) and fold left-to-right —
    the association is pinned by the expression tree, so the result is
    independent of how the blocks are distributed over devices. The
    shard-invariant replacement for `lax.psum` on the deterministic tree
    path (psum's reduction order is implementation-defined).

    ``timing_tag`` attaches the per-lane collective skew instrument
    (`mesh.lane_mark`, ISSUE 13): each lane stamps a host timestamp the
    moment its partial is ready, barrier-ordered before the all_gather, so
    the fence's per-lane waits are observable. Values are untouched (the
    mark is an identity + io_callback), preserving the bit-stability
    contract above. Only the per-scoring-interval callers pass a tag —
    the per-level histogram passes stay uninstrumented."""
    if axis_name is not None:
        if timing_tag is not None:
            from ..parallel import mesh as _mesh

            if _mesh.lane_timing_enabled():
                parts = _mesh.lane_mark(parts, axis_name, timing_tag)
        parts = jax.lax.all_gather(parts, axis_name, axis=0, tiled=False)
        parts = parts.reshape((-1,) + parts.shape[2:])
    acc = parts[0]
    for i in range(1, parts.shape[0]):
        acc = acc + parts[i]
    return acc


def feature_major(codes: jax.Array) -> jax.Array:
    """Full-width (N, F) codes as the feature-major float32 (F, N) array,
    rows on the lanes (bin codes are exact in float32). The factored
    Pallas kernel's operand and the partition step's select
    (`models/tree._row_codes`) both take it from HERE. Where a fit has a
    fit-lifetime operand (`build_code_operand`) neither calls it; elsewhere
    it is one expression of the program's loop-invariant codes, so XLA
    builds the buffer once per program and every reader streams the same
    one."""
    return codes.T.astype(jnp.float32)


def code_operand_shape(n_features: int, n_rows: int, row_chunk: int) -> tuple:
    """Shape of `build_code_operand`'s array: the features up to the Pallas
    kernel's 8-feature block, the rows up to a multiple of `row_chunk`."""
    from .hist_pallas import _FB

    return (-(-n_features // _FB) * _FB, -(-n_rows // row_chunk) * row_chunk)


# rows a step of `build_code_operand` widens: a multiple of every pack
# group, and small enough that the step's temporaries (a row-major float32
# block, 128 lanes a row whatever F is) stay ~0.13 GB
_OPERAND_BLOCK_ROWS = 1 << 18


@functools.partial(jax.jit, static_argnames=("pack_bits", "row_chunk"))
def build_code_operand(codes: jax.Array, pack_bits: int,
                       row_chunk: int) -> jax.Array:
    """The Pallas histogram kernel's code operand for a whole fit, from the
    resident codes (`ops.packing` words when `pack_bits`, else full width):
    `feature_major` of the widened codes, in the kernel's final form —
    features padded to its 8-feature block and rows to `row_chunk`
    (`code_operand_form`) with -1, which matches no bin — so that
    `hist_pallas.build_histograms_pallas_factored` has nothing left to pad
    a level. ONE program a fit (`shared_tree._fit_phases`, span
    `design.operand`); the tree programs take the result as an argument.
    The values are the ones the in-program widen produces: the same
    `unpack_device` and `feature_major`, over `_OPERAND_BLOCK_ROWS` rows
    at a time written into the output in place, so the program's
    temporaries are a block's and not the matrix's (at 11.5M x 28 the whole
    matrix at once reserves 7.4 GB beside its 1.5 GB result)."""
    F = codes.shape[1]
    N = (packing.packed_nrows(codes.shape[0], pack_bits) if pack_bits
         else codes.shape[0])
    block = min(_OPERAND_BLOCK_ROWS, N)
    stored = block * codes.shape[0] // N   # resident rows a block is stored in

    def widen(rows):
        if pack_bits:
            rows = packing.unpack_device(rows, pack_bits)
        return feature_major(rows)

    def step(i, out):
        rows = jax.lax.dynamic_slice_in_dim(codes, i * stored, stored)
        return jax.lax.dynamic_update_slice(out, widen(rows), (0, i * block))

    out = jnp.full(code_operand_shape(F, N, row_chunk), -1.0, jnp.float32)
    whole = N // block
    out = jax.lax.fori_loop(0, whole, step, out)
    if N > whole * block:
        out = jax.lax.dynamic_update_slice(
            out, widen(codes[whole * stored:]), (0, whole * block))
    return out


def _run_kernel(sel: dict, codes, node_id, vals, n_nodes: int, nbins: int,
                pack_bits: int, operand=None):
    """One resolved kernel invocation over one contiguous row range.
    `operand` is the fit's `build_code_operand` array where the fit has
    one: the Pallas kernel reads it and nothing is widened here."""
    method = sel["method"]
    if method == "pallas_factored" and operand is not None:
        from . import hist_pallas

        return hist_pallas.build_histograms_pallas_factored(
            operand, node_id, vals, n_nodes, nbins,
            row_chunk=sel["row_chunk"],
            n_features=codes.shape[1])
    if pack_bits:
        # these kernels take dense codes: widen in-graph. The widen is
        # a pure function of the loop-invariant packed input, so XLA
        # computes it once per program execution and shares the buffer
        # across every level's histogram pass; the RESIDENT matrix stays
        # packed
        codes = packing.unpack_device(codes, pack_bits)
    if method == "onehot":
        return _hist_onehot(codes, node_id, vals, n_nodes, nbins)
    if method == "segment":
        return _hist_segment(codes, node_id, vals, n_nodes, nbins)
    # "pallas_factored": `resolve_method` admits no other name
    from . import hist_pallas

    return hist_pallas.build_histograms_pallas_factored(
        feature_major(codes), node_id, vals, n_nodes, nbins,
        row_chunk=sel["row_chunk"],
    )


def _packed_row_slice(codes, r0: int, r1: int, pack_bits: int):
    """Rows [r0, r1) of a (possibly packed) code matrix. Block boundaries
    are multiples of 8 rows, so they always align with pack groups."""
    if not pack_bits:
        return codes[r0:r1]
    group = packing.GROUP_ROWS[pack_bits]
    gbytes = packing.GROUP_BYTES[pack_bits]
    return codes[r0 // group * gbytes: r1 // group * gbytes]


@jax.named_scope("tree.hist")
def build_histograms(
    codes: jax.Array,
    node_id: jax.Array,
    g: jax.Array,
    h: jax.Array,
    w: jax.Array,
    n_nodes: int,
    nbins: int,
    method: str = "auto",
    axis_name: Optional[str] = None,
    pack_bits: int = 0,
    n_shard_blocks: int = 0,
    operand: Optional[jax.Array] = None,
) -> jax.Array:
    """Histogram of {Σw, Σg, Σh} per (tree-node, feature, bin).

    Rows with w==0 (padding, row-sampling dropouts, OOB) contribute nothing —
    g/h/w must already be masked by the caller. `axis_name` triggers the
    cross-host merge (the MRTask.reduce step) when called under shard_map.

    With ``pack_bits`` in {4, 5, 6}, `codes` is the `ops.packing` packed
    matrix, widened in-graph before accumulating — unless `operand`, the
    fit's `build_code_operand` array, is given: the Pallas kernel then
    reads that and `codes` only where the level fell back to ``segment``.
    One row range only: the blocked reduction takes no operand.

    ``n_shard_blocks`` > 0 switches to the shard-invariant blocked
    reduction (see module docstring): this call's rows are split into that
    many equal contiguous blocks, each accumulated independently by the
    SAME kernel, and the partials fold deterministically across blocks and
    (under `axis_name`) across devices. The caller guarantees rows divide
    evenly (padded row counts are multiples of blocks·8).
    """
    vals = jnp.stack([w, g * w, h * w]).astype(jnp.float32)  # (3, N)
    sel = resolve_method(n_nodes, nbins, method)
    _record_selection(sel)
    if n_shard_blocks > 0:
        if operand is not None:
            raise ValueError("the blocked reduction takes no code operand")
        n = node_id.shape[0]
        if n % n_shard_blocks:
            raise ValueError(
                f"{n} rows do not divide into {n_shard_blocks} shard blocks")
        rows = n // n_shard_blocks
        parts = []
        for b in range(n_shard_blocks):
            parts.append(_run_kernel(
                sel, _packed_row_slice(codes, b * rows, (b + 1) * rows,
                                       pack_bits),
                node_id[b * rows:(b + 1) * rows],
                vals[:, b * rows:(b + 1) * rows],
                n_nodes, nbins, pack_bits))
        return ordered_axis_fold(jnp.stack(parts), axis_name)
    hist = _run_kernel(sel, codes, node_id, vals, n_nodes, nbins, pack_bits,
                       operand)
    if axis_name is not None:
        hist = jax.lax.psum(hist, axis_name)
    return hist  # (n_nodes, F, nbins, 3) — [..., 0]=Σw [..., 1]=Σg [..., 2]=Σh
