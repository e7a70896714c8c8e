"""The trace reduction: busy union, idle share, kernel time by event name;
on hand-made intervals, and on a small trace recorded on a TPU v5e
(``data/trace_small``, written by ``bench/record_trace.py``)."""
import re

import pytest

from lib import roofline, rundata, trace
from conftest import DATA

OPS = {"/device:TPU:0": [
    (0.0, 100.0, "%while.1 = (s32[]) while(s32[] %a)"),
    (10.0, 20.0, "%_descend.3 = s32[16]{0} custom-call(s32[3]{0} %b)"),
    (30.0, 50.0, "%fusion.7 = f32[4]{0} fusion(f32[4]{0} %c)"),
    (40.0, 60.0, "%_descend.3 = s32[16]{0} custom-call(s32[3]{0} %b)"),
    (150.0, 170.0, "%fusion.8 = f32[4]{0} fusion(f32[4]{0} %c)"),
    (300.0, 310.0, "%_descend_other = s32[2]{0} fusion(s32[2]{0} %d)"),
]}


def test_union_idle_and_kernel_time_by_name():
    assert trace.busy_ns(OPS, 0, 400) == 100 + 20 + 10
    assert trace.busy_ns(OPS, 50, 160) == 50 + 10
    assert trace.kernel_ns(OPS, roofline.DESCENT_OP, 0, 400) == 10 + 20
    assert trace.kernel_ns(OPS, roofline.DESCENT_OP, 35, 400) == 20
    view = rundata.TraceView(ops=OPS, host=[], start_ns=0.0,
                             window_end_ns=400.0, stop_ns=400.0,
                             offset_ns=0.0)
    assert view.window_s() == 400e-9
    assert abs(view.busy_s() - 130e-9) < 1e-18
    gaps = trace.idle_gaps(OPS, [(90.0, 400.0, "$server.py _dispatch",
                                  "python")], 0, 400)
    assert [round(g[1] * 1e9) for g in gaps] == [130, 90, 50]
    assert "_dispatch" in gaps[0][0]


def test_op_labels():
    assert rundata.opcode(OPS["/device:TPU:0"][0][2]) == "while"
    assert rundata.op_label(OPS["/device:TPU:0"][1][2]) == \
        "%_descend.3 custom-call"


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace_small" / "trace.xplane.pb"
    pd = trace.load(path)
    return pd, trace.device_ops(pd)


def test_recorded_trace_reduces(recorded):
    pd, ops = recorded
    assert list(ops) == ["/device:TPU:0"]
    evs = ops["/device:TPU:0"]
    t0, t1 = evs[0][0], max(e for _, e, _ in evs)
    # every op of the traced searches lies inside one of the XLA modules
    mods = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
            for p in pd.planes if p.name == "/device:TPU:0"
            for line in p.lines if line.name == "XLA Modules"
            for e in line.events]
    busy = trace.busy_ns(ops, t0, t1)
    assert 0 < busy <= trace.union_ns(mods, t0, t1) + 1
    assert busy <= t1 - t0
    kernel = [(s, e) for s, e, n in evs
              if re.search(roofline.DESCENT_OP, n)]
    assert kernel and all("custom-call(" in n for s, e, n in evs
                          if re.search(roofline.DESCENT_OP, n))
    assert trace.kernel_ns(ops, roofline.DESCENT_OP, t0, t1) == \
        sum(e - s for s, e in kernel)
    assert trace.kernel_ns(ops, roofline.DESCENT_OP, t0, t1) < busy
    assert trace.clock_mark_ns(pd) < t0
    assert trace.top_ops(ops, t0, t1, 3)[0][1] > 0
