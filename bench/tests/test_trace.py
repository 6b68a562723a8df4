"""The trace reduction: interval arithmetic and labelling on a built trace,
and `load` on a small trace recorded on a TPU v5e."""
import pathlib

import pytest

from bench import trace as tl

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _trace():
    ops = [[("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", 0, 10),
            ("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %p)", 5, 20),
            ("%while.3 = (s32[], bf16[8]{0}) while((s32[], bf16[8]) %t)",
             40, 60),
            ("%closed_call.4 = bf16[8,4]{1,0} custom-call(s32[8]{0} %b)",
             42, 58),
            ("%copy.5 = bf16[8]{0} copy(bf16[8]{0} %q)", 90, 100)]]
    programs = [[("jit__unknown(1)", 0, 20), ("jit_scatter(7)", 30, 35),
                 ("jit__unknown(2)", 40, 60), ("jit__unknown(3)", 90, 100)]]
    spans = [("bench.window", 0, 100), ("bench.step", 0, 35),
             ("bench.readback_wait", 22, 30), ("bench.wait_arrival", 60, 90)]
    return tl.Trace(ops, programs, sorted(spans, key=lambda s: s[1]), [])


CALLS = [("decode", [(10, 8)]), ("megastep", [(11, 8), (12, 8)], 2),
         ("prefill", [(5, 8)])]


def test_union_subtract_total():
    assert tl.union([(5, 20), (0, 10), (30, 40)]) == [(0, 20), (30, 40)]
    assert tl.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) == \
        [(0, 10), (30, 90)]
    assert tl.total(tl.clip([(0, 20), (30, 40)], 10, 35)) == 15


def test_programs_matched_to_calls_in_order():
    tr = _trace()
    progs = tl.step_programs(tr, 0, 0, 100, ("jit__unknown",))
    assert [p[0] for p in progs] == ["jit__unknown(1)", "jit__unknown(2)",
                                     "jit__unknown(3)"]
    assert tl.step_ns(progs, CALLS, ("decode", "megastep"))[0] == 40
    assert tl.step_ns(progs, CALLS, ("prefill",)) == (10, [(90, 100)])


def test_programs_must_pair_with_calls():
    progs = tl.step_programs(_trace(), 0, 0, 100, ("jit__unknown",))
    named = lambda names: [(n, s, e) for (_, s, e), n in zip(progs, names)]
    assert tl.matched(progs, CALLS)
    two_decodes = [CALLS[0], CALLS[0], CALLS[2]]
    assert tl.matched(named(["a", "a", "b"]), two_decodes)
    # one program would be both a decode and a prefill
    assert not tl.matched(named(["a", "b", "a"]), CALLS)
    # the same program for megasteps of two K
    assert not tl.matched(named(["a", "b", "b"]),
                          [CALLS[0], CALLS[1], CALLS[1][:2] + (4,)])
    assert not tl.matched(progs[:2], CALLS)


def test_ops_by_opcode_and_label():
    tr = _trace()
    assert tl.total(tl.busy(tr, 0, 0, 100)) == 20 + 20 + 10
    _, spans = tl.step_ns(tl.step_programs(tr, 0, 0, 100, ("jit__",)),
                          CALLS, ("decode", "megastep"))
    assert tl.ops_inside(tr, 0, spans, "custom-call") == [(42, 58)]
    assert tl.opcode(tr.ops[0][2][0]) == "while"
    assert tl.op_label(tr.ops[0][3][0]) == "closed_call.4 bf16[8,4] custom-call"
    top = tl.top_ops(tr, 0, 100)
    assert top[0] == ["closed_call.4 bf16[8,4] custom-call", 16e-9]
    assert all("while" not in name for name, _ in top)


def test_ops_by_instruction_prefix():
    kernel = "%paged_attn_decode.11 = bf16[4,32,1,96]{3,2,1,0} custom-call("
    other = "%grouped_matmul.2 = bf16[8,64]{1,0} custom-call("
    gather = "%paged_attn_decode.3 = bf16[4,32]{1,0} fusion("
    tr = tl.Trace([[(kernel, 10, 20), (other, 20, 35), (gather, 35, 40),
                    (kernel, 50, 60), (kernel, 70, 80)]], [[]], [], [])
    spans = [(0, 45), (50, 65)]
    assert tl.ops_inside(tr, 0, spans, "custom-call",
                         prefix="paged_attn_decode") == [(10, 20), (50, 60)]
    assert tl.ops_inside(tr, 0, spans, "custom-call") == \
        [(10, 20), (20, 35), (50, 60)]


def test_idle_gaps_labelled_by_innermost_span():
    gaps = tl.idle_gaps(_trace(), 0, 0, 100)
    assert gaps[0] == ["bench.wait_arrival", 30e-9]
    assert ["bench.readback_wait", 20e-9] in gaps
    assert len(gaps) == 2


def test_idle_share_leaves_out_waiting():
    from bench.metrics import idle_share
    tr = _trace()
    got = idle_share.read({"trace": tr, "lo": 0, "hi": 100})
    # in flight: 0-60 and 90-100 (70 ns), busy in it: 20 + 20 + 10
    assert got == pytest.approx(100 * (1 - 50 / 70))


@pytest.mark.skipif(not (DATA / "small.xplane.pb").exists(),
                    reason="no recorded trace")
def test_load_recorded_tpu_trace():
    tr = tl.load(str(DATA / "small.xplane.pb"))
    assert len(tr.ops) == 1 and tr.ops[0]
    names = {n for n, _, _ in tr.spans}
    assert {"bench.window", "bench.step"} <= names
    win = tl.span_intervals(tr, "bench.window")
    lo, hi = win[0][0], win[-1][1]
    busy = tl.total(tl.busy(tr, 0, lo, hi))
    assert 0 < busy <= hi - lo
    # the device's clock runs ~1.5 ms behind the host's here, so programs
    # are matched over the whole trace, which syncs bracket
    assert len(tl.step_programs(tr, 0, 0, 2 ** 62, ("matmul_step",))) == 3
    gaps = tl.idle_gaps(tr, 0, lo, hi)
    assert gaps and all(g[0].startswith("bench.") or g[0] == "none"
                        for g in gaps)
