"""The pipeline's device time by stage (``harness.stages``) and the two
readers built on it, on hand-built traces and on small traces recorded on
one v5e: ``data/tiny.xplane.pb`` from a program without stage scopes
(``record_trace.py``) and ``data_scoped/tiny_scoped.xplane.pb`` from one
with them (``record_scoped_trace.py``: 20000 keys, a quarter-second
closed loop)."""
import collections
import json
import pathlib
from types import SimpleNamespace as NS

import pytest
from jax.profiler import ProfileData

from harness import cell_run, pipeline, stages, xplane
from harness.spec import Bench

HERE = pathlib.Path(__file__).resolve().parent
DATA = HERE / "data"
SCOPED_DIR = HERE / "data_scoped"
SCOPED = SCOPED_DIR / "tiny_scoped.xplane.pb"
SCOPES = ("plex.route", "plex.segment", "plex.probe", "plex.fold")

HLO = "\n".join([
    "ENTRY %main (p: u32[8]) -> s32[8] {",
    '  %p = u32[8]{0} parameter(0), metadata={op_name="args[0]"}',
    '  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f1, '
    'metadata={op_name="jit(traced)/plex.route/le"}',
    '  %fusion.2 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f2, '
    'metadata={op_name="jit(traced)/plex.segment/jit(_take)/gather"}',
    '  %fusion.3 = u32[8]{0} fusion(%p), kind=kCustom, calls=%f3, '
    'metadata={op_name="jit(traced)/plex.probe/jit(_take)/gather" '
    'stack_frame_id=4}',
    '  %copy-start = (u32[8]{0}) copy-start(%p)',
    '  ROOT %add.1 = s32[8]{0} add(%fusion.3, %fusion.2), '
    'metadata={op_name="jit(traced)/plex.fold/add"}',
    "}"])


def _ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def _op(instr, start_ns, dur_ns):
    return _ev(f"%{instr} = s32[8]{{0}} fusion(%p)", start_ns, dur_ns)


def _pd():
    """One device: two pipeline calls (0-100, 200-300 ns), one other
    program (400-500 ns), and their ops."""
    mods = [_ev("jit_traced(7)", 0, 100), _ev("jit_traced(7)", 200, 100),
            _ev("jit_other(9)", 400, 100)]
    ops = [_op("fusion.1", 0, 10), _op("fusion.2", 10, 20),
           _op("fusion.3", 30, 60), _op("add.1", 90, 5),
           _op("copy-start", 95, 5),
           _op("fusion.1", 200, 10), _op("fusion.3", 210, 80),
           _op("unknown.9", 290, 10),
           _op("fusion.3", 400, 100)]         # the other program's
    dev = NS(name="/device:TPU:0",
             lines=[NS(name=xplane.MODULES_LINE, events=mods),
                    NS(name=xplane.OPS_LINE, events=ops)])
    return NS(planes=[NS(name="/host:CPU", lines=[]), dev])


def test_stage_seconds_by_module_and_window():
    hlo = {"jit_traced(7)": HLO, "jit_other(9)": HLO}
    by = stages.stage_seconds(_pd(), hlo, None)
    assert by == pytest.approx({
        "plex.route": 20e-9, "plex.segment": 20e-9, "plex.probe": 140e-9,
        "plex.fold": 5e-9, stages.UNSCOPED: 15e-9})
    # an op counts whole where it overlaps the window
    by = stages.stage_seconds(_pd(), hlo, (150.0, 295.0))
    assert by == pytest.approx({"plex.route": 10e-9, "plex.probe": 80e-9,
                                stages.UNSCOPED: 10e-9})


def test_instruction_of_an_op_event():
    assert stages.instruction(
        "%fusion.22 = (s32[4096]{0:T(1024)}) fusion(%a), kind=kLoop") == \
        "fusion.22"
    assert stages.instruction("%copy-start.3 = (u32[4]) copy-start(%x)") \
        == "copy-start.3"


def _reader(name):
    return Bench().reader(name)


@pytest.mark.parametrize("metric, scopes", [
    ("probe_ns_per_lookup.batch", ("plex.probe",)),
    ("segment_ns_per_lookup.batch", ("plex.route", "plex.segment"))])
def test_stage_readers(monkeypatch, metric, scopes):
    by = {"plex.route": 0.5, "plex.segment": 1.5, "plex.probe": 8.0,
          "plex.fold": 0.25, stages.UNSCOPED: 0.01}
    monkeypatch.setattr(stages, "run_stages", lambda bench_dir: by)
    rec = {"trace": {"busy_s": 10.0}, "attempted": 1_000_000}
    read = _reader(metric)
    assert read(rec) == pytest.approx(
        sum(by[s] for s in scopes) * 1e9 / rec["attempted"])
    # untraced, or a program without the scopes: nothing to read
    assert read(dict(rec, trace=None)) is None
    monkeypatch.setattr(stages, "run_stages",
                        lambda bench_dir: {stages.UNSCOPED: 10.0})
    assert read(rec) is None


def test_unscoped_program_reads_nothing():
    """A trace of a program older than the scopes holds its HLO, every
    instruction unscoped; the stage metrics then read nothing."""
    pd = ProfileData.from_file(str(DATA / "tiny.xplane.pb"))
    hlo = stages.module_hlo(DATA / "tiny.xplane.pb")
    assert [k.split("(")[0] for k in hlo] == [pipeline.PIPELINE_MODULE]
    by = stages.stage_seconds(pd, hlo,
                              xplane.find_event(pd, stages.WINDOW_SPAN))
    assert set(by) == {stages.UNSCOPED}
    spans = json.loads((DATA / "tiny_spans.json").read_text())
    seconds, _ = pipeline.pipeline_device(cell_run.reduce_trace(DATA, spans))
    assert by[stages.UNSCOPED] == pytest.approx(seconds, rel=0.01)


@pytest.fixture(scope="module")
def scoped():
    pd = ProfileData.from_file(str(SCOPED))
    spans = json.loads((SCOPED_DIR / "tiny_scoped_spans.json").read_text())
    return NS(pd=pd, hlo=stages.module_hlo(SCOPED), spans=spans,
              window=xplane.find_event(pd, stages.WINDOW_SPAN),
              red=cell_run.reduce_trace(SCOPED_DIR, spans))


def test_scoped_trace_time_is_under_the_scopes(scoped):
    """At least 95% of the pipeline's device time lies under a ``plex.*``
    scope, and the stages together are the module's time."""
    by = stages.stage_seconds(scoped.pd, scoped.hlo, scoped.window)
    assert set(SCOPES) <= set(by)
    total = sum(by.values())
    assert sum(by[s] for s in SCOPES) >= 0.95 * total
    seconds, _ = pipeline.pipeline_device(scoped.red)
    assert total == pytest.approx(seconds, rel=0.02)


def test_trace_hlo_agrees_with_the_program(scoped):
    """The stage of every op that ran, read from the HLO the trace
    carries, is the one the program's own map
    (``PlexService.stage_of_ops``) gives its instruction."""
    program = json.loads(
        (SCOPED_DIR / "tiny_scoped_stages.json").read_text())
    (name, text), = scoped.hlo.items()
    assert name.split("(")[0] == pipeline.PIPELINE_MODULE
    from_trace = stages.stage_of_hlo(text)
    ran = {stages.instruction(op) for op in scoped.red["ops"]}
    assert ran and ran <= set(from_trace)
    assert {i: from_trace[i] for i in ran} == {i: program[i] for i in ran}


def test_serve_spans_are_host_events(scoped):
    """The program's ``serve.*`` spans are in the trace's host plane under
    their own names, with no anchor: each ``serve.lookup`` inside the
    harness's ``bench.lookup`` call and around its staging, dispatch and
    sync."""
    host = collections.defaultdict(list)
    for s, e, n in xplane.host_events(scoped.pd):
        host[n].append((s, e))
    calls = sorted(host["bench.lookup"])
    lookups = sorted(host["serve.lookup"])
    assert len(lookups) == len(calls) > 0
    for (cs, ce), (s, e) in zip(calls, lookups):
        assert cs <= s <= e <= ce
    for child in ("serve.staging", "serve.dispatch", "serve.sync"):
        assert len(host[child]) == len(lookups)
        for (s, e), (ls, le) in zip(sorted(host[child]), lookups):
            assert ls <= s <= e <= le


def test_readers_on_the_scoped_trace(scoped, tmp_path):
    """Both readers on the recorded run's trace, laid out as
    ``cell_run.measure`` leaves it: the probe's time per lookup is most of
    the pipeline's, and the two stages together stay under it."""
    trace = tmp_path.joinpath(*stages.TRACE_SUBDIR)
    trace.mkdir(parents=True)
    (trace / SCOPED.name).write_bytes(SCOPED.read_bytes())
    attempted = sum(ev["attrs"]["n"] for ev in scoped.spans
                    if ev["name"] == "serve.lookup")
    rec = {"trace": scoped.red, "attempted": attempted}
    probe = stages.ns_per_lookup(rec, tmp_path, ("plex.probe",))
    segment = stages.ns_per_lookup(rec, tmp_path,
                                   ("plex.route", "plex.segment"))
    seconds, _ = pipeline.pipeline_device(scoped.red)
    pipe_ns = seconds * 1e9 / attempted
    assert 0 < segment < probe and probe + segment <= pipe_ns * 1.001
    assert probe >= 0.5 * pipe_ns
