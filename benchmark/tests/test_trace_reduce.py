"""The reduction from a trace to numbers, on a hand-made trace whose answers
can be worked out on paper and on a trace recorded on the v5e in this PR."""

import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
TESTDATA = os.path.join(os.path.dirname(HERE), "testdata")


def text_trace(devices: dict, host: list) -> str:
    """devices: plane name -> [(op name, start us, length us, tf_op)];
    host: [(event name, start us, length us)].  As in a real trace, an op's
    ``tf_op`` is a stat of its metadata, so an op name has one ``tf_op``."""
    planes = []
    for pid, (plane, ops) in enumerate(devices.items(), 1):
        ids = {o[0]: i for i, o in enumerate(
            {o[0]: o for o in ops}.values(), 1)}
        tf_ops = {o[0]: o[3] for o in ops}
        events = "".join(
            f'events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e6)} '
            f'duration_ps: {int(d * 1e6)} }}\n' for n, s, d, _ in ops)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" stats '
            f'{{ metadata_id: 1 str_value: "{tf_ops[n]}" }} }} }}\n'
            for n, i in ids.items())
        planes.append(
            f'planes {{ id: {pid} name: "{plane}"\n lines {{ id: 1 name: '
            f'"XLA Ops" timestamp_ns: 0\n{events} }}\n{meta}'
            f'stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}')
    ids = {n: i for i, n in enumerate(dict.fromkeys(h[0] for h in host), 1)}
    events = "".join(f'events {{ metadata_id: {ids[n]} offset_ps: '
                     f'{int(s * 1e6)} duration_ps: {int(d * 1e6)} }}\n'
                     for n, s, d in host)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                   f'"{n}" }} }}\n' for n, i in ids.items())
    planes.append(f'planes {{ id: 99 name: "/host:CPU"\n lines {{ id: 1 name: '
                  f'"python" timestamp_ns: 0\n{events} }}\n{meta} }}')
    return "\n".join(planes)


def reduce_text(text):
    from jax.profiler import ProfileData

    return trace_reduce.reduce_profile(trace_reduce.parse_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


DENSE = "jit(f)/while/body/Block_0/MultiHeadAttention_0/Dense_0/dot_general"
ATTN = "jit(f)/while/body/Block_0/MultiHeadAttention_0/vmap(while)/body/dot_general"
MLP = "jit(f)/while/body/Block_0/Dense_1/dot_general"


def test_one_device_on_paper():
    # window = the two calls, 0..100 us and 120..200 us: 200 us.
    # a while of 80 us holds a matmul (30), an attention op (20) and an
    # elementwise op (10): 20 us of its own.  A second while of 60 us in the
    # second call holds one 60 us matmul.  Busy 140, idle 60: 20 before the
    # first op, 20 between the calls, 20 at the end of the second call.
    s = reduce_text(text_trace(
        {"/device:TPU:0": [
            ("while.1", 20, 80, "jit(f)/while"),
            ("fusion.1", 25, 30, MLP),
            ("fusion.2", 60, 20, ATTN),
            ("fusion.3", 85, 10, "jit(f)/while/body/add"),
            ("while.1", 120, 60, "jit(f)/while"),
            ("fusion.9", 120, 60, DENSE),
        ]},
        [("bench.call", 0, 100), ("bench.call", 120, 80)]))
    assert s.calls == 2 and s.window_ns == pytest.approx(200e3)
    assert s.busy_s == pytest.approx(140e-6)
    assert s.idle_share() == pytest.approx(0.30)
    assert s.all_idle_share() == pytest.approx(0.30)
    assert s.class_share("matmul") == pytest.approx(90 / 140)
    assert s.class_share("attention") == pytest.approx(20 / 140)
    assert s.class_share("loop") == pytest.approx(20 / 140)
    assert s.class_share("other") == pytest.approx(10 / 140)
    gaps = dict(s.host_gaps)
    assert gaps["bench.call"] == pytest.approx(40e-6)
    assert gaps["between calls"] == pytest.approx(20e-6)
    top = dict(s.breakdown()["device_ops"])
    assert top["fusion.9 [] MultiHeadAttention_0/Dense_0/dot_general"] == pytest.approx(60e-6)


def test_two_devices_collective_exposed_and_hidden():
    # device 0: compute 0..60, all-reduce 50..80 (10 hidden, 20 exposed)
    # device 1: compute 0..40, all-reduce 40..80 (all 40 exposed)
    s = reduce_text(text_trace(
        {"/device:TPU:0": [("fusion.1", 0, 60, MLP),
                           ("all-reduce.1", 50, 30, "jit(f)/psum")],
         "/device:TPU:1": [("fusion.1", 0, 40, MLP),
                           ("all-reduce.1", 40, 40, "jit(f)/psum")]},
        [("bench.call", 0, 100)]))
    d0, d1 = s.devices
    assert d0.collective_ns == pytest.approx(30e3)
    assert d0.collective_exposed_ns == pytest.approx(20e3)
    assert d1.collective_exposed_ns == pytest.approx(40e3)
    assert s.busy_s == pytest.approx(80e-6)  # both busy 0..80
    assert s.idle_share() == pytest.approx(0.2)


def test_ops_outside_the_calls_do_not_count_and_no_call_is_an_error():
    s = reduce_text(text_trace(
        {"/device:TPU:0": [("fusion.1", 0, 50, MLP), ("fusion.1", 100, 50, MLP)]},
        [("bench.call", 90, 100)]))
    assert s.busy_s == pytest.approx(50e-6)
    with pytest.raises(ValueError, match="annotation"):
        reduce_text(text_trace({"/device:TPU:0": [("fusion.1", 0, 5, MLP)]},
                               [("something.else", 0, 10)]))


def test_importing_the_reducer_loads_no_jax():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); import benchmark.trace_reduce;"
            "assert 'jax' not in sys.modules" % os.path.dirname(
                os.path.dirname(HERE)))
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


def test_recorded_v5e_trace_reduces_to_pinned_numbers():
    """30 ms of ``gpt2l_silo_fused`` recorded on the v5e in PR 22 (depth 8),
    cut by ``tools/crop_trace.py`` around the end of the first traced call:
    the tail of one round, the readback, the next dispatch."""
    s = trace_reduce.reduce_trace(
        os.path.join(TESTDATA, "gpt2l_silo_fused_v5e_30ms.textproto"))
    assert s.calls == 2 and len(s.devices) == 1
    assert s.window_ns == pytest.approx(30e6)
    assert s.busy_s == pytest.approx(0.021782427736, rel=1e-9)
    assert s.idle_share() == pytest.approx(0.27391907546666516, rel=1e-9)
    assert s.all_idle_share() == pytest.approx(s.idle_share())
    assert s.class_share("matmul") == pytest.approx(0.349486959776226, rel=1e-9)
    assert s.class_share("attention") == pytest.approx(0.15932333751138292,
                                                       rel=1e-9)
    assert s.class_share("loop") == pytest.approx(0.0032148552883290993,
                                                  rel=1e-9)
    assert s.class_share("other") == pytest.approx(0.4904493075555613, rel=1e-9)
    assert s.devices[0].collective_ns == 0
    name, seconds = s.breakdown()["device_ops"][0]
    assert name.startswith("broadcast.8957 [broadcast]")
    assert seconds == pytest.approx(0.002053733749, rel=1e-9)
    what, gap = s.breakdown()["idle_gaps"][0]
    assert what == "np.asarray(jax.Array)"  # the device waits for the readback
    assert gap == pytest.approx(0.008212198671, rel=1e-9)
