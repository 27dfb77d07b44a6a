"""reduce_trace.py: the arithmetic on a hand-made trace, and the reading of a
small trace recorded on the chip (tests/data, recorded in the PR that added
the benchmark: one 200,000 x 28 GBM fit of 3 trees and one 100,000-row GLM
fit, both on one TPU v5 lite)."""

import glob
import os

import pytest

import reduce_trace
from reduce_trace import Trace, union_seconds

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    ops = [("fusion.1", 0.0, 1.0), ("hist", 0.5, 1.0),      # overlap: busy 0..1.5
           ("hist", 3.0, 0.5),                               # gap 1.5..3.0
           ("copy", 3.5, 0.25),                              # touches: no gap
           ("fusion.1", 6.0, 1.0)]                           # gap 3.75..6.0
    modules = [("jit_step(1)", 0.0, 1.5), ("jit_other(2)", 3.0, 0.75),
               ("jit_step(1)", 6.0, 1.0)]
    host = [("main", "train", 0.0, 7.0), ("main", "binning", 1.6, 1.2),
            ("worker", "upload", 3.8, 2.0), ("main", "blip", 4.0, 0.1)]
    return Trace([{"ops": ops, "modules": modules}], host)


def test_union_counts_overlap_once():
    assert union_seconds([(0.0, 1.0), (0.5, 1.0), (3.0, 0.5)]) == pytest.approx(2.0)
    assert union_seconds([]) == 0.0


def test_busy_names_programs_and_gaps():
    tr = hand_made()
    assert tr.busy_seconds() == pytest.approx(1.5 + 0.75 + 1.0)
    assert tr.op_seconds("^hist$") == pytest.approx(1.5)
    assert tr.by_name()["fusion.1"] == pytest.approx(2.0)
    steps = tr.program_events(r"^jit_step\(")
    assert steps == [(0.0, 1.5), (6.0, 1.0)]
    # between the two steps 4.5 s pass, 0.75 s of them busy with other work
    assert tr.idle_between(steps) == [pytest.approx(3.75)]
    assert tr.idle_gaps() == [(1.5, 3.0), (3.75, 6.0)]
    # with the window's span: nothing before the first op, 7.0 - 7.0 after
    assert tr.idle_gaps("train") == [(1.5, 3.0), (3.75, 6.0)]
    tr.host.append(("main", "win", -1.0, 9.0))
    assert tr.idle_gaps("win") == [(-1.0, 0.0), (1.5, 3.0), (3.75, 6.0), (7.0, 8.0)]


def test_gaps_are_named_by_what_the_host_was_doing():
    bd = hand_made().breakdown(top=10)
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    # longest gap first: 3.75..6.0 is mostly the upload, 1.5..3.0 the binning
    # (the innermost span that covers most of the gap, not the enclosing train)
    assert bd["idle_gaps"] == [["upload", pytest.approx(2.25)],
                               ["binning", pytest.approx(1.5)]]


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace_reduces(path):
    tr = reduce_trace.load(path)
    assert len(tr.devices) == 1 and tr.devices[0]["ops"] and tr.host
    busy = tr.busy_seconds()
    first = min(s for _, s, _ in tr.devices[0]["ops"])
    last = max(s + d for _, s, d in tr.devices[0]["ops"])
    assert 0 < busy <= last - first
    # the union is never more than the plain sum, and gaps fill the rest
    assert busy <= sum(d for _, _, d in tr.devices[0]["ops"]) + 1e-9
    assert busy + sum(b - a for a, b in tr.idle_gaps()) == pytest.approx(
        last - first, rel=1e-6)
    bd = tr.breakdown(top=10)
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
