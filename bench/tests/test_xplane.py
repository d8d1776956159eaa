"""The trace reduction on a small trace recorded on one v5e
(``record_trace.py``: 20000 keys, a quarter-second closed loop)."""
import json
import pathlib

import pytest
from jax.profiler import ProfileData

from harness import cell_run, pipeline, xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_file(str(DATA / "tiny.xplane.pb"))


@pytest.fixture(scope="module")
def red():
    spans = json.loads((DATA / "tiny_spans.json").read_text())
    return cell_run.reduce_trace(DATA, spans)


def test_device_plane_and_window(pd, red):
    assert red["devices"] == 1
    win = xplane.find_event(pd, "bench.window")
    assert red["window_s"] == pytest.approx((win[1] - win[0]) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]


def test_busy_is_the_union_of_op_intervals(pd, red):
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(line for line in dev.lines if line.name == xplane.OPS_LINE)
    lo, hi = xplane.find_event(pd, "bench.window")
    ivs = sorted((e.start_ns, e.start_ns + e.duration_ns)
                 for e in ops.events)
    busy, end = 0.0, lo
    for s, e in ivs:
        s, e = max(s, end), min(e, hi)
        if e > s:
            busy += e - s
            end = e
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert sum(red["ops"].values()) >= red["busy_s"]


def test_pipeline_found_by_its_trace_name(red):
    seconds, count = pipeline.pipeline_device(red)
    assert count > 0 and 0 < seconds <= red["busy_s"] * 1.001
    names = {k.split("(")[0] for k in red["modules"]}
    assert names == {pipeline.PIPELINE_MODULE}


def test_idle_time_is_put_down_to_host_spans(red):
    idle = red["idle_by_host"]
    total = red["window_s"] - red["busy_s"]
    assert sum(idle.values()) == pytest.approx(total, rel=1e-6)
    assert "(no host span)" not in idle


def test_program_spans_land_on_the_profiler_clock(pd):
    """The anchor pair puts the program's serve.* spans inside the
    harness's bench.lookup annotations that caused them."""
    spans = json.loads((DATA / "tiny_spans.json").read_text())
    anchor = xplane.find_event(pd, "bench.anchor")
    mine = next(e for e in spans if e["name"] == "bench.anchor")
    prog = cell_run.program_spans(
        [e for e in spans if e["name"] == "serve.lookup"],
        anchor[0] - mine["ts"] * 1e9)
    calls = sorted((s, e) for s, e, n in xplane.host_events(pd)
                   if n == "bench.lookup")
    assert len(prog) == len(calls) > 0
    for (s, e, _), (cs, ce) in zip(sorted(prog), calls):
        assert cs - 50e3 <= s and e <= ce + 50e3       # within 50 us


def test_breakdown_lists(red):
    b = xplane.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= xplane.TOP
        assert all(isinstance(n, str) and v > 0 for n, v in b[key])
        values = [v for _, v in b[key]]
        assert values == sorted(values, reverse=True)
    assert all("{" not in n for n, _ in b["device_ops"])
