"""The reduction from a profiler trace to busy time, idle share, kernel time
and idle gaps: on synthetic events, and on a real trace of a CPU run."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr

FLASH = ("%flash_attention_tpu.18 = bf16[2,12,4096,128]{3,2,1,0:T(8,128)(2,1)"
         "S(1)} custom-call(bf16[2,12,4096,128]{3,2,1,0:T(8,128)} %bitcast.841)")
SIL = ("%jvp_jit_sil_mse_fwd_tpu__.1 = (f32[64,3,1,128]{3,2,1,0:T(1,128)}, "
       "f32[8192,1536]{1,0:T(8,128)S(1)}) custom-call(s32[8192]{0} %b.869)")
WHILE = ("%while.147 = (s32[]{:T(128)}, bf16[2,4096,1536]{1,2,0:T(8,128)}) "
         "while((s32[]{:T(128)}, bf16[2,4096,1536]) %tuple.3), condition=%c")


@pytest.mark.parametrize("text,name,kind", [
    (FLASH, "flash_attention_tpu.18", "custom-call"),
    (SIL, "jvp_jit_sil_mse_fwd_tpu__.1", "custom-call"),
    (WHILE, "while.147", "while"),
    ("%fusion.562 = f32[2,4096]{1,0:T(8,128)} fusion(f32[2] %p), kind=kLoop",
     "fusion.562", "fusion"),
    ("ThunkExecutor::Execute", "ThunkExecutor::Execute", ""),
])
def test_hlo_name(text, name, kind):
    assert tr.hlo_name(text) == (name, kind)


def test_union_clips_and_merges():
    got = tr.union([(5, 8), (0, 3), (2, 4), (9, 20), (7, 8.5)], 1, 15)
    assert got == [(1, 4), (5, 8.5), (9, 15)]


def test_reduce_busy_idle_kernels_and_gaps():
    ms = 1e6
    # a container op spanning its children, two kernels, one op across the
    # window's start; the window is [10, 110] ms
    dev = [(WHILE, 10 * ms, 50 * ms),
           (FLASH, 12 * ms, 20 * ms),
           (FLASH.replace(".18", ".19"), 30 * ms, 40 * ms),
           (SIL, 60 * ms, 70 * ms),
           ("%fusion.1 = f32[2] fusion(f32[2] %p)", 0, 15 * ms),
           ("%fusion.2 = f32[2] fusion(f32[2] %p)", 80 * ms, 100 * ms)]
    host = [("window", 10 * ms, 110 * ms), ("dispatch", 50 * ms, 61 * ms),
            ("input", 52 * ms, 55 * ms), ("drain", 100 * ms, 110 * ms)]
    r = tr.reduce_events(dev, host, (10 * ms, 110 * ms))
    assert r.window_s == pytest.approx(0.100)
    # busy: [10, 50] + [60, 70] + [80, 100] = 70 ms
    assert r.busy_s == pytest.approx(0.070)
    assert r.idle_share == pytest.approx(0.30)
    assert r.kernel("flash_attention_tpu") == (2, pytest.approx(0.018))
    assert r.kernel("sil_mse_fwd_tpu") == (1, pytest.approx(0.010))
    assert r.kernel("no_such_kernel") == (0, 0.0)
    # the op crossing the window's start counts only inside it
    assert r.ops["fusion.1"] == (1, pytest.approx(0.005))
    # idle [50, 60]: its middle lies in dispatch and in input, the innermost
    # names it; [70, 80] lies under no span; [100, 110] under drain
    assert [g for g, _ in r.gaps] == ["input", "host", "drain"]
    assert [s for _, s in r.gaps] == pytest.approx([0.010] * 3)
    names = [n for n, _ in r.top_ops(10)]
    assert not any(n.startswith("while") for n in names)   # containers out
    assert names[0].startswith("fusion.2")


def test_reduce_trace_reads_a_real_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("dispatch"):
                y = f(x)
        with jax.profiler.TraceAnnotation("drain"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    red = tr.reduce_trace(str(tmp_path), device_plane="/host:CPU",
                          ops_line="tf_XLA")
    assert len(red) == 1
    r = red[0]
    assert 0 < r.busy_s <= r.window_s
    assert r.ops, "no XLA operations found in the window"
    assert all(g in ("dispatch", "drain", "host") for g, _ in r.gaps)


def test_reduce_trace_without_device_plane_fails(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="plane"):
        tr.reduce_trace(str(tmp_path))


def test_roofline_reads_the_family_costs_over_the_chips():
    """``trace.roofline`` (the kernel readers): the least time of the
    kernel's calls from the family's per-call cost over their device time,
    over every chip; silent where no call ran or the family has no cost."""
    from types import SimpleNamespace
    ms = 1e6
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

    def chip(n_calls, each_ms):
        dev = [(FLASH.replace(".18", f".{i}"), 10 * i * ms,
                (10 * i + each_ms) * ms) for i in range(n_calls)]
        return tr.reduce_events(dev, [], (0, 100 * ms))
    # one call: 4e9 FLOPs (4 ms at peak) and 2e8 bytes (2 ms): 4 ms least
    costs = {"flash_attention_tpu": (4e9, 2e8)}
    m = SimpleNamespace(traces=[chip(2, 8), chip(1, 5)], peak=peak,
                        kernel_costs=[costs, costs])
    assert tr.roofline(m, "flash_attention_tpu") == pytest.approx(
        100 * 3 * 4 / (2 * 8 + 5))
    assert tr.roofline(m, "sil_mse_fwd_tpu") is None
    m.kernel_costs = [{}, {}]
    assert tr.roofline(m, "flash_attention_tpu") is None
    m.traces = None
    assert tr.roofline(m, "flash_attention_tpu") is None
