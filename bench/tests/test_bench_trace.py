"""The trace reduction: busy union, idle share, attribution of launches
and ops to programs, and of idle gaps to host annotations; on a
synthetic trace laid out as a TPU profile is, and on a small trace
recorded on the CPU."""

from types import SimpleNamespace as NS

import pytest

from bench import devtrace

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=int(start_ms * MS),
              duration_ns=int(dur_ms * MS), stats=[])


def line(name, events):
    return NS(name=name, events=events)


# op names as a TPU v5e profile gives them: the instruction's HLO text
KERNEL = ('%run.8 = f32[802816,10]{1,0:T(8,128)} custom-call(f32[896,802816]'
          '{1,0:T(8,128)} %bitcast.410, f32[896,10]{1,0:T(8,128)S(1)} '
          '%copy-done.96), custom_call_target="tpu_custom_call"')
DOT = ('%convolution_bitcast_fusion = f32[896,896]{1,0:T(8,128)} fusion('
       'f32[896,896,896]{2,1,0:T(8,128)} %args_0_.1), kind=kOutput')
WHILE = ('%while.291 = (s32[]{:T(128)}, f32[896,896]{1,0:T(8,128)S(1)}) '
         'while((s32[]{:T(128)}, f32[896,896]) %tuple.9), body=%body.1')
EIGH = ('%custom-call.91 = (f32[128]{0:T(128)S(1)}, f32[128,128]{1,0:T(8,128)'
        '}) custom-call(f32[128,128]{1,0:T(8,128)S(1)} %g.1), '
        'custom_call_target="EighTpu"')


def synthetic():
    """A 100 ms traced window: a decode launch 10-40 ms (ops with a gap
    at 25-27; a while loop enclosing an eigh kernel), a prefill launch
    60-70 ms; the host ticks 0-50 and 55-100, decoding 5-45 and
    prefilling 55-72; one whole job 8-75."""
    device = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", [ev("jit_serve_decode(7)", 10, 30),
                             ev("jit_serve_prefill(9)", 60, 10)]),
        line("XLA Ops", [ev(DOT, 10, 15), ev(WHILE, 27, 13),
                         ev(EIGH, 28, 10), ev("%copy.3 = f32[8]{0} copy("
                                              "f32[8]{0} %a)", 60, 4),
                         ev(KERNEL, 63, 7)]),
    ])
    host = NS(name="/host:CPU", lines=[line("main", [
        ev("bench.window", 0, 100), ev("bench.tick", 0, 50),
        ev("bench.decode", 5, 40), ev("bench.tick", 55, 45),
        ev("bench.prefill_chunk", 55, 17), ev("bench.hooi", 8, 67),
        ev("other", 0, 100)])])
    return NS(planes=[host, device])


def test_union_and_gaps():
    assert devtrace.union_length([(0, 4), (2, 6), (8, 9)]) == 7
    assert devtrace._gaps([(1, 3), (2, 5)], 0, 10) == [(0, 1), (5, 10)]


def test_busy_idle_and_launches():
    r = devtrace.reduce_profile(synthetic())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((15 + 13 + 10) / 1e3)
    assert r["launches"] == {"serve_decode": [pytest.approx(0.03)],
                             "serve_prefill": [pytest.approx(0.01)]}
    assert r["op_s"][("serve_prefill", "run:tpu_custom_call")] == \
        pytest.approx(0.007)
    # the while loop encloses the eigh kernel: busy, but no op time
    assert r["op_s"][("serve_decode", "custom-call:EighTpu")] == \
        pytest.approx(0.010)
    assert not any(k[1].startswith("while") for k in r["op_s"])
    assert r["kernel_s"] == pytest.approx(0.007)            # Pallas only
    assert r["contract_s"] == pytest.approx(0.015 + 0.007)  # + XLA dot
    assert r["jobs"] == 1
    idle = dict(r["breakdown"]["idle_gaps"])
    # idle 0-10 and 25-27 fall in decode's annotation, the shortest that
    # holds them; 40-60 (midpoint 50) in the first tick, shorter than the
    # job; 70-100 in the second tick
    assert idle["bench.decode"] == pytest.approx(0.012)
    assert idle["bench.tick"] == pytest.approx(0.050)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["serve_decode/convolution_bitcast_fusion"] == \
        pytest.approx(0.015)


def test_hlo_parts():
    assert devtrace.hlo_parts(KERNEL) == ("run", "custom-call",
                                          "tpu_custom_call")
    assert devtrace.hlo_parts(WHILE)[:2] == ("while", "while")
    assert devtrace.hlo_parts(EIGH) == ("custom-call", "custom-call",
                                        "EighTpu")
    assert devtrace.is_contraction(DOT) and devtrace.is_kernel(KERNEL)
    assert not devtrace.is_contraction(EIGH)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError, match="no device ops"):
        devtrace.reduce_profile(NS(planes=[synthetic().planes[0]]))


def test_recorded_cpu_trace(tmp_path):
    """A profile recorded here: the benchmark's window and annotations
    are on the host plane, and a CPU has no device plane to reduce."""
    import jax
    import jax.numpy as jnp

    tr = devtrace.Tracer(tmp_path, start_s=0.0, seconds=60.0)
    tr.arm(0.0)
    assert tr.poll()
    with jax.profiler.TraceAnnotation("bench.decode"):
        jnp.ones((64, 64)).sum().block_until_ready()
    tr.finish()
    pd = devtrace.load(tmp_path)
    names = {e.name for p in pd.planes for ln in p.lines for e in ln.events}
    assert {"bench.window", "bench.decode"} <= names
    with pytest.raises(ValueError, match="no device ops"):
        devtrace.reduce_profile(pd)
