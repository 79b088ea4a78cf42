"""The trace reduction on a small hand-made trace: busy union, idle gaps
labelled by the open span, step programs and kernel sums by name."""
from bench import trace


def _trace():
    ms = 1e-3
    device = {
        "XLA Modules": [("jit_step_fn(1)", 1 * ms, 5 * ms),
                        ("jit_step_fn(1)", 7 * ms, 10 * ms)],
        "XLA Ops": [
            ("%while.1 = (s32[]) while(...)", 1 * ms, 5 * ms),
            ("%paged_decode_attention.7 = bf16[8] custom-call(...)", 1 * ms, 2 * ms),
            ("%ragged_ffn.3 = bf16[8] custom-call(...)", 2 * ms, 4 * ms),
            ("%convert.1 = bf16[4] convert(...)", 4 * ms, 5 * ms),
            ("%paged_decode_attention.7 = bf16[8] custom-call(...)", 7 * ms, 8.5 * ms),
            ("%ragged_ffn.3 = bf16[8] custom-call(...)", 8.5 * ms, 10 * ms),
        ],
    }
    host = [("bench.window", 0.0, 12 * ms),
            ("bench.step", 0.5 * ms, 5.5 * ms),
            ("bench.collect", 5.5 * ms, 6.5 * ms),
            ("bench.step", 6.5 * ms, 10.5 * ms)]
    return device, host


def test_union_and_gaps():
    assert trace.union([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert trace.union([(0, 2), (1, 3)], 1.5, 2.5) == 1.0
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.gaps([(0, 6)], 0, 6) == []


def test_reduce():
    ms = 1e-3
    r = trace.reduce(*_trace(), "step_fn",
                     {"paged_attn": "paged_decode_attention",
                      "moe_ffn": "ragged_ffn"})
    assert abs(r["window_s"] - 12 * ms) < 1e-12
    assert abs(r["busy_s"] - 7 * ms) < 1e-12          # [1,5] + [7,10]
    got = [(n, round(d / ms, 9)) for n, d in r["idle_gaps"]]
    assert got == [("bench.step", 1.0), ("bench.collect", 2.0),
                   ("host (no bench span)", 2.0)]
    assert len(r["modules"]) == 2
    s0, s1 = r["steps"]
    assert abs(s0["device_s"] - 4 * ms) < 1e-12
    assert abs(s0["kernel_s"]["paged_attn"] - 1 * ms) < 1e-12
    assert abs(s0["kernel_s"]["moe_ffn"] - 2 * ms) < 1e-12
    assert abs(s1["kernel_s"]["paged_attn"] - 1.5 * ms) < 1e-12
    assert s1["kernel_calls"] == {"paged_attn": 1, "moe_ffn": 1}
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert not any(n.startswith("while") for n in names)
    assert names[0].startswith("ragged_ffn.3")        # 2 + 1.5 ms
