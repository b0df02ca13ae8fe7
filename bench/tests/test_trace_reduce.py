"""The trace reduction: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (bench/tests/record_trace.py) with its numbers
worked out by hand from the events listed below."""
import os
import shutil

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_clip_and_gaps():
    iv = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 25)]
    assert tr.union(iv) == [(0, 3), (5, 12), (20, 25)]
    busy = tr.union(tr.clip(iv, 2, 22))
    assert busy == [(2, 3), (5, 12), (20, 22)]
    # 20 units of window, 10 busy: idle [3, 5), [12, 20)
    assert sum(e - s for s, e in busy) == 10
    assert tr.gaps(busy, 2, 22) == [(3, 5), (12, 20)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_gaps_named_by_innermost_host_span():
    host = tr.HostSpans([("window", 0, 100), ("engine.step", 10, 40),
                         ("sleep", 50, 90)])
    idle = [(10, 14), (60, 80), (95, 100)]
    named = tr.name_gaps(idle, host, top=2)
    # the two longest: 20 ns under "sleep", 5 ns under "window"; the 4 ns
    # gap is summed apart
    assert named == pytest.approx({"sleep": 20e-9, "window": 5e-9,
                                   "(shorter gaps)": 4e-9})


def test_hlo_shapes_and_family():
    text = ("%custom-call.3 = f32[512,384]{1,0} custom-call(bf16[512,256]{1,0} "
            "%p0, bf16[256,384]{1,0} %p1, u32[512,384]{1,0} %z)")
    assert tr.hlo_shapes(text) == [("f32", (512, 384)), ("bf16", (512, 256)),
                                   ("bf16", (256, 384)), ("u32", (512, 384))]
    assert tr.op_family("fusion.12") == "fusion"
    assert tr.op_family("custom-call_3") == "custom-call"


def _call(result, *operands):
    ops = ", ".join(f"{d}[{','.join(map(str, s))}]{{1,0}} %p{i}"
                    for i, (d, s) in enumerate(operands))
    d, s = result
    return (f"%sr_matmul.7 = {d}[{','.join(map(str, s))}]{{1,0}} custom-call({ops}),"
            f' custom_call_target="tpu_custom_call", backend_config="..."')


@pytest.mark.parametrize("bits", [True, False])
@pytest.mark.parametrize("kernel,result,a,b", [
    ("sr_matmul", (512, 384), (512, 256), (256, 384)),      # FF
    ("sr_matmul", (512, 256), (512, 384), (256, 384)),      # BP, trans_b
    ("outer_accum", (256, 384), (2048, 256), (2048, 384)),  # UP
    ("sr_matmul", (512, 256), (512, 512), (512, 256)),      # FF or UP: T = K
    (None, (512, 384), (384, 256), (256, 512)),             # fits neither
])
def test_kernel_told_by_shapes_with_or_without_entropy(kernel, result, a, b, bits):
    """A kernel is recognised by its result and first two operands, so a
    call that drops the uint32 entropy operand is still the same kernel."""
    operands = [("bf16", a), ("bf16", b)] + ([("u32", result)] if bits else [])
    ev = tr.kernel_event(_call(("f32", result), *operands), 2e-6)
    assert ev == {"kernel": kernel, "seconds": 2e-6,
                  "shapes": [("f32", result), ("bf16", a), ("bf16", b)]}
    assert tr.kernel_event("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p0)", 1.0) is None


# The recorded trace (TPU v5e, one chip), event times in ns:
#   host span window      45593576 .. 140597267        (95003691)
#   host span host.sleep  67560304 .. 118530539
#   device ops, three groups:
#     elementwise fusion  65376021 .. 65388835         (12814)
#     sr_matmul program   117443345 .. 117446399: copy-start 4, 2, broadcast
#                         131, copy-done 551, 260, the Pallas call 2100
#                         (gaps of 2, 1, 2, 1 between them)
#     outer_accum program 118320640 .. 118323999: 4, 2, 131, 543, 262, the
#                         Pallas call 2411 (gaps of 2, 1, 1, 2)
WINDOW_NS = 95003691
BUSY_NS = 12814 + (4 + 2 + 131 + 551 + 260 + 2100) + (4 + 2 + 131 + 543 + 262 + 2411)
# idle: 19782445 before the first op and 22273268 after the last (host
# span "window"); 52054510 and 874241 between the groups plus the twelve
# nanoseconds between ops, all inside "host.sleep"
SLEEP_IDLE_NS = 52054510 + 874241 + 12
WINDOW_IDLE_NS = 19782445 + 22273268


@pytest.fixture(scope="module")
def small():
    return tr.reduce_file(DATA)


def test_recorded_trace_busy_and_idle(small):
    assert BUSY_NS == 19215
    assert small["window_s"] == pytest.approx(WINDOW_NS * 1e-9, abs=1e-12)
    assert small["busy_s"] == pytest.approx(BUSY_NS * 1e-9, abs=1e-12)
    assert BUSY_NS + SLEEP_IDLE_NS + WINDOW_IDLE_NS == WINDOW_NS
    assert dict(small["breakdown"]["idle_gaps"]) == pytest.approx(
        {"host.sleep": SLEEP_IDLE_NS * 1e-9, "window": WINDOW_IDLE_NS * 1e-9},
        abs=1e-12)


def test_recorded_trace_ops_and_kernels(small):
    ops = dict(small["breakdown"]["device_ops"])
    assert ops["multiply_add_fusion"] == pytest.approx(12814e-9, abs=1e-15)
    assert ops["copy-done"] == pytest.approx((551 + 260 + 543 + 262) * 1e-9, abs=1e-15)
    got = [(k["kernel"], round(k["seconds"] * 1e9), k["shapes"])
           for k in small["kernels"]]
    assert got == [
        ("sr_matmul", 2100, [("f32", (512, 384)), ("bf16", (512, 256)),
                             ("bf16", (256, 384))]),
        ("outer_accum", 2411, [("f32", (512, 384)), ("bf16", (256, 512)),
                               ("bf16", (256, 384))])]
    assert small["pallas_unmatched"] == {"calls": 0, "seconds": 0.0}
    assert len(small["modules"]) == 3


def test_recorded_trace_roofline(small):
    from bench import roofline
    rec = {"kind": "train", "device_kind": "TPU v5 lite", "trace": small}
    # sr_matmul: 2*512*256*384 = 100,663,296 FLOPs -> 0.511 us at 197e12;
    # bf16 bytes 2*(512*256 + 256*384 + 512*384) = 851,968 -> 1.0403 us at
    # 819e9: memory-bound, 1.0403 / 2.100 us
    assert roofline.share(rec, "sr_matmul") == pytest.approx(
        100 * 851968 / 819e9 / 2100e-9)
    assert roofline.bound_by(rec, "sr_matmul") == {"memory": 1}
    # outer_accum: X (256 x 512), dY (256 x 384): the same counts, 2.411 us
    assert roofline.share(rec, "outer_accum") == pytest.approx(
        100 * 851968 / 819e9 / 2411e-9)


def test_a_kernel_is_a_file(tmp_path):
    """A Pallas call is the kernel whose file claims it: a new file adds a
    kernel ((A B)^T here, a call the two kernels do not claim), and a call
    that two files claim stops the reduction."""
    from bench import roofline
    for name in ("sr_matmul", "outer_accum"):
        shutil.copy(os.path.join(roofline.KERNELS_DIR, f"{name}.py"), tmp_path)
    (tmp_path / "swapped.py").write_text(
        "def match(shapes):\n"
        "    (_, out), (_, a), (_, b) = shapes[:3]\n"
        "    return a[1] == b[0] and out == (b[1], a[0])\n\n\n"
        "def cost(shapes):\n"
        "    return 0.0, 0.0\n")
    files = roofline.kernels(str(tmp_path))
    shapes = [("f32", (512, 384)), ("bf16", (384, 256)), ("bf16", (256, 512))]
    assert tr.classify(shapes, files) == "swapped"
    assert tr.classify(shapes) is None
    shutil.copy(tmp_path / "sr_matmul.py", tmp_path / "sr_matmul_again.py")
    files = roofline.kernels(str(tmp_path))
    with pytest.raises(RuntimeError, match=r"\['sr_matmul', 'sr_matmul_again'\]"):
        tr.classify([("f32", (512, 384)), ("bf16", (512, 256)),
                     ("bf16", (256, 384))], files)


def test_recorded_trace_with_a_kernel_claimed_twice(tmp_path, monkeypatch):
    from bench import roofline
    for name in ("sr_matmul", "outer_accum"):
        shutil.copy(os.path.join(roofline.KERNELS_DIR, f"{name}.py"), tmp_path)
    shutil.copy(tmp_path / "outer_accum.py", tmp_path / "outer_accum_again.py")
    monkeypatch.setattr(roofline, "KERNELS_DIR", str(tmp_path))
    with pytest.raises(RuntimeError, match="outer_accum_again"):
        tr.reduce_file(DATA)
