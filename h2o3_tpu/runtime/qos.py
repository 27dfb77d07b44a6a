"""Multi-tenant QoS — the cooperative two-class dispatch gate (ISSUE 19).

One runtime holding a live serving fleet AND a background AutoML sweep on
the same accelerator needs a priority scheduler between them; this module
is that scheduler, in its cheapest honest form: a cooperative gate with
two classes, SERVING > TRAINING.

- **Serving dispatches never wait.** `serving_dispatch()` registers a
  scoring batch (batcher) or router forward as in flight — entry is
  non-blocking, always. The serving path's latency is never a function of
  what training is doing between its own dispatches.
- **Training yields at safe boundaries.** The tree driver calls
  `yield_point()` at chunk boundaries and before each scoring-event
  dispatch, the streamed tree step at per-BLOCK visits, and the estimator
  engine between bounded `while_loop` segments
  (`estimator_engine.max_iters_per_dispatch`). While any serving dispatch
  is in flight (or within a short linger window after one — back-to-back
  requests keep priority across their gaps), the yield blocks instead of
  enqueueing the next training program behind which a serving batch would
  otherwise queue.
- **Anti-starvation floor** (``H2O3_QOS_TRAIN_MIN_SHARE``): a training
  thread's cumulative wait is bounded so that
  ``ran / (ran + waited) >= share`` — under SUSTAINED serving load
  training still makes forward progress at roughly the configured share
  of wall-clock, one bounded wait per yield. ``H2O3_QOS_MAX_WAIT_MS``
  additionally caps any single wait (a progress backstop against a leaked
  in-flight count).
- **Admission throttle** (`admission_gate`, consulted by `trainpool`
  before each candidate): a hysteresis state machine over ONE
  `pressure_view()` snapshot and the live serving p99 read from the
  central registry (``h2o3_rest_request_ms{handler=predict}``): enter
  throttled at ``pressure >= H2O3_QOS_PRESSURE_HI`` OR
  ``p99 >= H2O3_QOS_SLO_MS * H2O3_QOS_P99_RATIO_HI``; exit only at
  ``pressure <= H2O3_QOS_PRESSURE_LO`` AND
  ``p99 <= SLO * H2O3_QOS_P99_RATIO_LO``. Every transition is a counter
  bump + gauge flip + trace event.
- **One pressure snapshot** (`pressure_view()`): serving admission and
  `dataset_cache._evict_locked` both read the ledger's pressure through
  this single consistent view, so a scrape-time refresh between their two
  reads can never shed serving scorers while admitting training work.
  Within one view ``shed_serving`` implies ``evict_cache`` (0.97 vs 0.9
  default thresholds): training artifacts always shed BEFORE serving does.

QoS is DEFAULT-OFF (``H2O3_QOS=1`` arms it) and changes WHEN programs
dispatch, never what they compute — every bit-exactness pin holds with the
gate armed (pinned in tests/test_qos.py).

Observability: ``h2o3_qos_yields{site}``, ``h2o3_qos_waits_ms{site}``,
``h2o3_qos_throttle_state``, ``h2o3_qos_throttle_transitions{state}``,
``h2o3_qos_preempt_latency_ms`` registry families; waits booked into the
``qos_wait`` phase bucket (subtracted from the enclosing compute bucket at
sites that would otherwise double-book); `stats()` is the /3/Profiler
``qos`` fold; `gate_state()` names the class holding the gate.

Fault points (runtime/faults.py, REST-armable, ``match=`` scoped):
``qos.starve`` (error="none") makes every yield see a closed gate —
sustained-serving simulation proving the min-share floor; and
``qos.preempt_delay`` (error="none", latency_ms=X) injects latency at the
yield itself.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, NamedTuple, Optional

from . import env_float
from . import faults as _faults

__all__ = ["enabled", "serving_dispatch", "yield_point", "admission_gate",
           "pressure_view", "PressureView", "serving_p99_ms", "throttled",
           "gate_state", "totals", "stats", "reset", "train_min_share"]


# -- knobs (read per call: tests flip env live) --------------------------------

def enabled() -> bool:
    """``H2O3_QOS=1`` arms the gate; default off (free: one env read)."""
    import os

    return os.environ.get("H2O3_QOS", "").lower() in ("1", "true", "yes")


def train_min_share() -> float:
    """Anti-starvation floor: training is entitled to at least this
    fraction of its own (ran + waited) wall under sustained serving
    load."""
    return min(max(env_float("H2O3_QOS_TRAIN_MIN_SHARE", 0.1), 0.0), 0.9)


def _linger_s() -> float:
    return env_float("H2O3_QOS_LINGER_MS", 5.0) / 1e3


def _max_wait_s() -> float:
    return env_float("H2O3_QOS_MAX_WAIT_MS", 1000.0) / 1e3


# -- gate state ---------------------------------------------------------------

_CV = threading.Condition()
_SERVING_INFLIGHT = 0
_LAST_SERVING_END = 0.0       # monotonic; 0 = never served
_LAST_SERVING_DETAIL = ""
# most recent training yield (any thread): the watchdog's holder verdict
# and the preempt-latency proxy both read it — plain dict, GIL-atomic
_LAST_TRAIN_YIELD = {"t": 0.0, "site": ""}
_TLS = threading.local()      # per-training-thread share ledger

_TLOCK = threading.Lock()
_TOTALS = {"yields": 0, "waits_ms": 0.0, "serving_dispatches": 0,
           "throttle_transitions": 0, "throttle_waits_ms": 0.0}
_THROTTLE = {"state": 0, "since": 0.0}
_VIEW: Dict[str, object] = {"view": None}

_REG: dict = {}


def _reg() -> dict:
    """Memoized registry families (registration never on the hot path)."""
    if not _REG:
        from . import metrics_registry as reg

        _REG["yields"] = reg.counter(
            "h2o3_qos_yields",
            "training yield-point visits, per site (tree_chunk/tree_block/"
            "score_event/est_segment)", labelnames=("site",))
        _REG["waits"] = reg.histogram(
            "h2o3_qos_waits_ms",
            "time training waited at a yield point for the serving class "
            "(ms), per site", labelnames=("site",))
        _REG["throttle_state"] = reg.gauge(
            "h2o3_qos_throttle_state",
            "trainpool admission throttle: 1 = candidate admission paused "
            "(pressure/serving-p99 hysteresis), 0 = open")
        _REG["transitions"] = reg.counter(
            "h2o3_qos_throttle_transitions",
            "admission-throttle state transitions", labelnames=("state",))
        _REG["preempt"] = reg.histogram(
            "h2o3_qos_preempt_latency_ms",
            "age of training's most recent yield point when a serving "
            "dispatch arrived (ms) — the cooperative bound on how long "
            "serving could wait for training to yield the device")
    return _REG


def _gate_closed(now: float) -> bool:
    """Training must hold back: a serving dispatch is in flight, one just
    finished (linger window — back-to-back requests keep priority across
    their gaps), or the chaos lane armed sustained-serving simulation."""
    if _SERVING_INFLIGHT > 0:
        return True
    if _LAST_SERVING_END and (now - _LAST_SERVING_END) < _linger_s():
        return True
    return _faults.is_armed("qos.starve")


def _tls_state() -> dict:
    st = getattr(_TLS, "share", None)
    if st is None:
        st = _TLS.share = {"ran_s": 0.0, "waited_s": 0.0, "t_resume": 0.0}
    return st


# -- the two classes ----------------------------------------------------------

@contextmanager
def serving_dispatch(detail: str = ""):
    """Register a serving-class dispatch (batcher batch, router forward).

    NEVER waits — serving's only relationship to the gate is to close it
    for training while in flight. Entry also records the preempt-latency
    proxy: the age of training's most recent yield point, i.e. the
    cooperative upper bound on how long this request could have sat
    behind a training program had the gate not held it back."""
    global _SERVING_INFLIGHT, _LAST_SERVING_END, _LAST_SERVING_DETAIL
    if not enabled():
        yield
        return
    now = time.monotonic()
    lt = _LAST_TRAIN_YIELD
    if lt["t"] and (now - lt["t"]) < 5.0:
        try:
            _reg()["preempt"].observe((now - lt["t"]) * 1e3)
        except Exception:
            pass
    with _CV:
        _SERVING_INFLIGHT += 1
        _LAST_SERVING_DETAIL = detail
    with _TLOCK:
        _TOTALS["serving_dispatches"] += 1
    try:
        yield
    finally:
        with _CV:
            _SERVING_INFLIGHT -= 1
            _LAST_SERVING_END = time.monotonic()
            if _SERVING_INFLIGHT <= 0:
                _CV.notify_all()


def yield_point(site: str = "train",
                compensate: Optional[str] = None) -> float:
    """Training-class safe boundary: wait here while serving is in flight.

    Returns seconds waited (0.0 when QoS is off or the gate is open). The
    wait is bounded by the min-share floor — a thread that has computed
    ``ran`` seconds and already waited ``waited`` may wait at most
    ``ran·(1/share − 1) − waited`` more, so training always converges to
    its configured share under sustained load — and by
    ``H2O3_QOS_MAX_WAIT_MS`` per visit. `compensate` names a phase bucket
    the wait would otherwise be double-booked into (the tree driver's
    chunk marks, the estimator engine's ``est_iter``); the wait is booked
    to ``qos_wait`` and subtracted there."""
    if not enabled():
        return 0.0
    now = time.monotonic()
    st = _tls_state()
    if st["t_resume"]:
        st["ran_s"] += max(now - st["t_resume"], 0.0)
    st["t_resume"] = now
    _LAST_TRAIN_YIELD["t"] = now
    _LAST_TRAIN_YIELD["site"] = site
    with _TLOCK:
        _TOTALS["yields"] += 1
    try:
        _reg()["yields"].inc(1, site)
    except Exception:
        pass
    # injected preemption delay (latency-only fault point; never raises
    # when armed with error="none")
    _faults.check("qos.preempt_delay", site)
    if not _gate_closed(time.monotonic()):
        return 0.0
    share = train_min_share()
    if share > 0:
        budget = st["ran_s"] * (1.0 / share - 1.0) - st["waited_s"]
    else:
        budget = _max_wait_s()
    budget = min(max(budget, 0.0), _max_wait_s())
    if budget <= 0:
        return 0.0
    t0 = time.monotonic()
    deadline = t0 + budget
    with _CV:
        while True:
            now2 = time.monotonic()
            if now2 >= deadline or not _gate_closed(now2):
                break
            # wake on serving release; poll quanta cover linger expiry
            # and a mid-wait qos.starve disarm
            _CV.wait(min(deadline - now2, 0.05))
    waited = time.monotonic() - t0
    st["waited_s"] += waited
    st["t_resume"] = time.monotonic()
    with _TLOCK:
        _TOTALS["waits_ms"] += waited * 1e3
    try:
        _reg()["waits"].observe(waited * 1e3, site)
    except Exception:
        pass
    from . import phases as _phases

    _phases.add("qos_wait", waited)
    if compensate:
        _phases.add(compensate, -waited)
    return waited


# -- one consistent pressure snapshot -----------------------------------------

class PressureView(NamedTuple):
    """One ledger pressure read with BOTH shed decisions evaluated at the
    same instant — `shed_serving` (admission's 429 threshold) can never be
    true while `evict_cache` (the dataset cache's training-artifact shed)
    is false, because the eviction threshold sits below the serving one:
    training artifacts always go first."""

    value: float
    shed_serving: bool
    evict_cache: bool
    at: float

    def decide(self, threshold: float) -> bool:
        """This snapshot's value against a caller-local threshold (the
        serving config's `shed_pressure` may be constructed, not env)."""
        return threshold > 0 and self.value >= threshold


def pressure_view(max_age_s: Optional[float] = None) -> PressureView:
    """The shared pressure snapshot. With QoS armed, views are cached for
    ``H2O3_QOS_PRESSURE_VIEW_S`` (default 0.2 s) so admission and eviction
    decisions inside one contended burst agree on a single value; with QoS
    off every call takes a fresh (ledger-side rate-limited) read — exactly
    the pre-QoS behavior, minus the two-sites-two-reads race."""
    from . import memory_ledger as ml

    if max_age_s is None:
        max_age_s = (env_float("H2O3_QOS_PRESSURE_VIEW_S", 0.2)
                     if enabled() else 0.0)
    now = time.monotonic()
    v = _VIEW.get("view")
    if (isinstance(v, PressureView) and max_age_s > 0
            and (now - v.at) < max_age_s):
        return v
    p = float(ml.pressure())
    shed_at = env_float("H2O3_SERVING_SHED_PRESSURE", 0.97)
    view = PressureView(p, shed_at > 0 and p >= shed_at,
                        p >= ml.evict_threshold(), now)
    _VIEW["view"] = view
    return view


def serving_p99_ms() -> Optional[float]:
    """Live end-to-end predict p99 from the central registry
    (``h2o3_rest_request_ms{handler=predict}``) — None before any predict
    has been served in this process."""
    try:
        from . import metrics_registry as reg

        h = reg.get("h2o3_rest_request_ms")
        if h is None:
            return None
        return h.percentile(0.99, "predict")
    except Exception:
        return None


# -- trainpool admission throttle ---------------------------------------------

def _eval_throttle() -> bool:
    """One hysteresis step; returns the (possibly new) throttled state and
    records every transition (counter + gauge + trace event)."""
    p_hi = env_float("H2O3_QOS_PRESSURE_HI", 0.9)
    p_lo = env_float("H2O3_QOS_PRESSURE_LO", 0.75)
    slo = env_float("H2O3_QOS_SLO_MS", 0.0)
    r_hi = env_float("H2O3_QOS_P99_RATIO_HI", 2.0)
    r_lo = env_float("H2O3_QOS_P99_RATIO_LO", 1.5)
    view = pressure_view()
    p99 = serving_p99_ms() if slo > 0 else None
    cur = _THROTTLE["state"]
    hot_latency = bool(slo > 0 and p99 is not None and p99 >= slo * r_hi)
    cool_latency = (slo <= 0 or p99 is None or p99 <= slo * r_lo)
    if cur == 0:
        new = 1 if (view.value >= p_hi or hot_latency) else 0
    else:
        new = 0 if (view.value <= p_lo and cool_latency) else 1
    if new != cur:
        _THROTTLE["state"] = new
        _THROTTLE["since"] = time.monotonic()
        with _TLOCK:
            _TOTALS["throttle_transitions"] += 1
        try:
            _reg()["throttle_state"].set(float(new))
            _reg()["transitions"].inc(1, "on" if new else "off")
        except Exception:
            pass
        try:
            from . import tracing as _tracing

            _tracing.event("qos_throttle", state="on" if new else "off",
                           pressure=round(view.value, 4),
                           serving_p99_ms=p99)
        except Exception:
            pass
    return bool(new)


def throttled() -> bool:
    """Current admission-throttle verdict (one hysteresis evaluation)."""
    if not enabled():
        return False
    return _eval_throttle()


def admission_gate(label: str = "candidate") -> float:
    """Trainpool's per-candidate admission hook: while the throttle is
    closed (pressure or serving-p99 hysteresis), hold the candidate back —
    bounded by ``H2O3_QOS_THROTTLE_MAX_WAIT_S`` so a sweep can never
    deadlock on a stuck gauge. Returns seconds waited."""
    if not enabled() or not _eval_throttle():
        return 0.0
    max_wait = env_float("H2O3_QOS_THROTTLE_MAX_WAIT_S", 5.0)
    poll = max(env_float("H2O3_QOS_THROTTLE_POLL_MS", 50.0), 1.0) / 1e3
    t0 = time.monotonic()
    deadline = t0 + max_wait
    while time.monotonic() < deadline and _eval_throttle():
        time.sleep(poll)
    waited = time.monotonic() - t0
    with _TLOCK:
        _TOTALS["throttle_waits_ms"] += waited * 1e3
    try:
        _reg()["waits"].observe(waited * 1e3, f"admission:{label}")
    except Exception:
        pass
    from . import phases as _phases

    _phases.add("qos_wait", waited)
    return waited


# -- observability ------------------------------------------------------------

def gate_state() -> Dict:
    """Who holds the gate right now:
    'serving' while any serving dispatch is in flight, 'training' while
    training yielded recently (it is between yields, i.e. inside its own
    dispatch burst), 'idle' otherwise."""
    now = time.monotonic()
    lt = dict(_LAST_TRAIN_YIELD)
    if _SERVING_INFLIGHT > 0:
        holder = "serving"
    elif lt["t"] and (now - lt["t"]) < 5.0:
        holder = "training"
    else:
        holder = "idle"
    out = dict(enabled=enabled(), holder=holder,
               serving_inflight=int(_SERVING_INFLIGHT),
               throttled=bool(_THROTTLE["state"]))
    if holder == "serving" and _LAST_SERVING_DETAIL:
        out["serving_detail"] = _LAST_SERVING_DETAIL
    if lt["t"]:
        out["last_training_site"] = lt["site"] or None
        out["last_training_yield_age_s"] = round(now - lt["t"], 3)
    return out


def totals() -> Dict:
    """Process-cumulative QoS counters."""
    with _TLOCK:
        t = dict(_TOTALS)
    t["waits_ms"] = round(t["waits_ms"], 3)
    t["throttle_waits_ms"] = round(t["throttle_waits_ms"], 3)
    return t


def stats() -> Dict:
    """The /3/Profiler ``qos`` fold: gate + throttle state, cumulative
    yield/wait totals, and the live knob values. Pure read."""
    out = dict(enabled=enabled(), gate=gate_state(), totals=totals(),
               throttle=dict(state=int(_THROTTLE["state"]),
                             since_s=(round(time.monotonic()
                                            - _THROTTLE["since"], 3)
                                      if _THROTTLE["since"] else None)),
               train_min_share=train_min_share())
    p99 = serving_p99_ms()
    if p99 is not None:
        out["serving_p99_ms"] = round(p99, 3)
    return out


def reset() -> None:
    """Zero the cumulative counters and gate/throttle state (tests;
    registry families are monotone and stay)."""
    global _SERVING_INFLIGHT, _LAST_SERVING_END, _LAST_SERVING_DETAIL
    with _TLOCK:
        _TOTALS.update(yields=0, waits_ms=0.0, serving_dispatches=0,
                       throttle_transitions=0, throttle_waits_ms=0.0)
    with _CV:
        _SERVING_INFLIGHT = 0
        _LAST_SERVING_END = 0.0
        _LAST_SERVING_DETAIL = ""
        _CV.notify_all()
    _LAST_TRAIN_YIELD.update(t=0.0, site="")
    _THROTTLE.update(state=0, since=0.0)
    _VIEW["view"] = None
    _TLS.share = None
