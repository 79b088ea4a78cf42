"""The split of device-idle gaps by engine span on a small hand-made
trace, the readers of that split, and ``trace.reduce`` reading what it
always read."""
import pytest

from bench import engine_trace, run, trace
from bench.tests.test_trace import _trace

MS = 1e-3


def _engine_trace():
    """Three step programs; the host spans of the steps around them,
    with the driver's own stretches between the steps."""
    modules = [(1 * MS, 5 * MS), (12 * MS, 16 * MS), (20 * MS, 24 * MS)]
    # the step programs' operations, and a small one inside the first gap
    ops = [(1 * MS, 5 * MS), (8 * MS, 8.5 * MS), (12 * MS, 16 * MS),
           (20 * MS, 24 * MS)]

    def step(t0, phases):
        out, t = [], t0
        for name, dur in zip(("engine_schedule", "engine_rows",
                              "engine_launch", "engine_wait",
                              "engine_samples", "engine_publish"), phases):
            out.append((name, t * MS, (t + dur) * MS))
            t += dur
        return out, t

    s1, t1 = step(0.2, (0.2, 0.4, 0.4, 4.3, 1.0, 0.5))      # ends at 7.0
    s2, t2 = step(10.0, (0.5, 1.0, 0.8, 3.9, 0.8, 0.5))     # ends at 17.5
    s3, t3 = step(18.5, (0.5, 0.6, 0.8, 4.0, 0.5, 0.5))
    engine = ([("engine_step", 0.2 * MS, 9.0 * MS)] + s1    # bare from 7 to 9
              + [("engine_step", 10.0 * MS, t2 * MS)] + s2
              + [("engine_step", 18.5 * MS, t3 * MS)] + s3
              + [("bench.step", 0.0, 30 * MS)])             # not an engine span
    return engine, ops, modules


def test_engine_phases_split_each_gap_by_the_innermost_span():
    got = engine_trace.engine_phases(*_engine_trace())
    # gap 1 (5, 12), busy 8-8.5: wait 0.5, samples 1.0, publish 0.5, bare
    # step 1.0 + 0.5 -> post 3.5; driver 9-10; schedule 0.5; rows 1.0;
    # launch 11.5-12 0.5.  gap 2 (16, 20): wait 0.2, samples 0.8, publish
    # 0.5 -> post 1.5; driver 17.5-18.5; schedule 0.5; rows 0.6; launch 0.4
    want = {"schedule": 1.0, "rows": 1.6, "launch": 0.9, "post": 5.0,
            "driver": 2.0}
    assert got["gaps"] == 2
    for part, ms in want.items():
        assert got[part] == pytest.approx(ms * MS, abs=1e-12), part
    parts = sum(got[p] for p in engine_trace.PARTS)
    assert parts == pytest.approx(got["idle_s"], abs=1e-12)
    assert got["idle_s"] == pytest.approx(10.5 * MS, abs=1e-12)


def test_engine_phases_read_nothing_without_engine_spans():
    engine, ops, modules = _engine_trace()
    bench_only = [sp for sp in engine if sp[0] not in engine_trace.PART]
    assert engine_trace.engine_phases(bench_only, ops, modules) is None
    assert engine_trace.engine_phases(engine, ops, modules[:1]) is None


@pytest.mark.parametrize("cells", ["serve", "chat"])
def test_idle_readers(monkeypatch, cells):
    monkeypatch.setattr(engine_trace, "load", lambda _dir: _engine_trace()[:2])
    engine, ops, modules = _engine_trace()
    rec = {"trace": {"modules": modules}}
    want = {"idle_schedule_ms": 0.5, "idle_rows_ms": 0.8,
            "idle_launch_ms": 0.45, "idle_post_ms": 2.5,
            "idle_driver_ms": 1.0}
    for quantity, ms in want.items():
        assert run.reader(f"{quantity}.{cells}")(rec) == pytest.approx(ms)
    # read from the profile once, kept on the record
    assert rec["engine_phases"]["gaps"] == 2


def test_reduce_reads_what_it_always_read():
    r = trace.reduce(*_trace(), "step_fn",
                     {"paged_attn": "paged_decode_attention",
                      "moe_ffn": "ragged_ffn"})
    assert r == {
        "breakdown": {
            "device_ops": [
                ["ragged_ffn.3 = bf16[8] custom-call(...)",
                 0.0034999999999999996],
                ["paged_decode_attention.7 = bf16[8] custom-call(...)",
                 0.0025000000000000005],
                ["convert.1 = bf16[4] convert(...)", 0.001]],
            "idle_gaps": [["bench.collect", 0.002],
                          ["host (no bench span)", 0.002],
                          ["bench.step", 0.001]]},
        "busy_s": 0.007,
        "idle_gaps": [("bench.step", 0.001), ("bench.collect", 0.002),
                      ("host (no bench span)", 0.002)],
        "modules": [(0.001, 0.005), (0.007, 0.01)],
        "steps": [
            {"device_s": 0.004, "host": (0.0005, 0.0055),
             "kernel_calls": {"moe_ffn": 1, "paged_attn": 1},
             "kernel_s": {"moe_ffn": 0.002, "paged_attn": 0.001},
             "modules": [(0.001, 0.005)]},
            {"device_s": 0.003, "host": (0.006500000000000001, 0.0105),
             "kernel_calls": {"moe_ffn": 1, "paged_attn": 1},
             "kernel_s": {"moe_ffn": 0.0014999999999999996,
                          "paged_attn": 0.0015000000000000005},
             "modules": [(0.007, 0.01)]}],
        "window": (0.0, 0.012),
        "window_s": 0.012}
