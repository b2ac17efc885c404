"""The reduction from a profiler trace to metrics, on small traces made
by hand, and on one recorded on the CPU."""
import pytest

from bench import flops, harness, serving, tracing
from bench.harness import Run, Tick
from bench.tracing import Device

MS = 1e6     # ns


def trace_of(devices, spans):
    return tracing.from_events(devices, spans +
                               [("bench.traced", 0.0, 100 * MS)])


def test_clip_busy_idle_and_gaps():
    dev = Device(
        modules=[("jit_decode_slots", 10 * MS, 30 * MS),
                 ("jit_prefill_slot", 50 * MS, 60 * MS),
                 ("jit_decode_slots", 95 * MS, 120 * MS)],
        ops=[("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 15 * MS, 28 * MS),
             ("flash_attention", 50 * MS, 60 * MS),
             ("fusion.1", 95 * MS, 120 * MS)])
    spans = [("bench.tick", 5 * MS, 35 * MS),
             ("bench.wait_arrival", 35 * MS, 48 * MS),
             ("bench.tick", 48 * MS, 62 * MS),
             ("bench.tick", 90 * MS, 130 * MS)]
    tr = trace_of({"/device:TPU:0": dev}, spans)
    assert tr.window_s == pytest.approx(0.1)
    # busy: 10-28, 50-60, 95-100 (clipped) = 33 ms
    assert tracing.busy_s(tr, "/device:TPU:0") == pytest.approx(0.033)
    assert tracing.idle_share(tr, "/device:TPU:0") == pytest.approx(0.67)
    assert tracing.program_time(tr, "/device:TPU:0", "jit_decode_slots") \
        == (pytest.approx(0.025), 2)
    assert tracing.op_time(tr, "/device:TPU:0",
                           lambda n: "flash" in n) == (pytest.approx(0.01), 1)
    gaps = tracing.idle_gaps(tr, "/device:TPU:0")
    # 0-10 (tick opens at 5: mid 5 -> tick), 28-50 (mid 39: waiting),
    # 60-95 (mid 77.5: nothing open)
    assert gaps == pytest.approx({"bench.tick": 0.01,
                                  "bench.wait_arrival": 0.022, "none": 0.035})
    bd = tracing.breakdown(tr)
    assert bd["device_ops"][0] == ["jit_decode_slots", pytest.approx(0.025)]
    assert len(bd["idle_gaps"]) == 3


def test_stable_names():
    assert tracing.stable_name("jit_decode_slots(1234)") == "jit_decode_slots"
    assert tracing.stable_name("fusion.12") == "fusion.12"


def test_busy_share_averages_over_chips():
    a = Device([], [("x", 0.0, 50 * MS)])
    b = Device([], [("x", 0.0, 100 * MS)])
    tr = trace_of({"/device:TPU:0": a, "/device:TPU:1": b}, [])
    assert tracing.mean_busy_s(tr) == pytest.approx(0.075)
    run = Run("w", {}, {"kind": "ring_rounds"}, "TPU v5 lite", 2)
    run.trace = tr
    # the highest idle share over the chips
    assert harness.load_reader("device_idle_pct.msg")(run) == \
        pytest.approx(50.0)


def load(config="qwen2-0.5b"):
    """(arch, spec) of a served configuration file, as the harness loads
    them."""
    path = f"bench/configs/{config}.json"
    return serving.load_arch(
        harness.load_json(f"{harness.ROOT}/{path}"), path)


def test_device_readers_on_a_made_trace():
    arch, sp = load()
    dev = Device(modules=[("jit_prefill_slot", 0.0, 10 * MS),
                          ("jit_decode_slots", 20 * MS, 30 * MS)],
                 ops=[("flash_attention", 1 * MS, 2 * MS)])
    run = Run("w", {}, {"kind": "open_loop"}, "TPU v5 lite", 1, spec=sp,
              arch=arch)
    run.trace = trace_of({"/device:TPU:0": dev}, [])
    run.ticks = [Tick(0.0, 0.031, [512], 3, 1500, traced=True),
                 Tick(0.031, 0.05, [384], 3, 1500, traced=False)]
    pk = flops.PEAKS["TPU v5 lite"]
    r = harness.load_reader
    assert r("prefill_mfu_pct")(run) == pytest.approx(
        100 * arch.prefill_flops(sp, 512) / (0.01 * pk["bf16_flops"]))
    assert r("decode_hbm_pct")(run) == pytest.approx(
        100 * arch.decode_bytes(sp, 1500) / (0.01 * pk["hbm_bytes_per_s"]))
    f, b = flops.flash_cost(14, 2, 64, 512)
    assert r("flash_roofline")(run) == pytest.approx(
        100 * 24 * max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
        / 0.001)
    # nothing to read: nothing returned, never a 0
    run.trace = trace_of({"/device:TPU:0": Device([], [])}, [])
    for name in ("prefill_mfu_pct", "decode_hbm_pct", "flash_roofline"):
        assert r(name)(run) is None


def test_permute_per_round():
    dev = Device(modules=[("jit_ring_round", i * MS, i * MS + 0.5 * MS)
                          for i in range(10)],
                 ops=[("collective-permute-start.3", i * MS, i * MS + 20e3)
                      for i in range(10)] +
                     [("collective-permute-done.3", i * MS + 30e3,
                       i * MS + 40e3) for i in range(10)])
    run = Run("w", {}, {"kind": "ring_rounds"}, "TPU v5 lite", 1)
    run.trace = trace_of({"/device:TPU:0": dev}, [])
    assert harness.load_reader("permute_us_per_round")(run) == \
        pytest.approx(30.0)


# per layer, the weights one token multiplies through: q and o, k and v,
# and the MLP (three matrices gated, two not)
QWEN2_LAYER = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
STARCODER2_LAYER = 2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432


# the counts of each served configuration, pinned to what they were when
# they were kept in bench/flops.py: per-layer readers divide by them
@pytest.mark.parametrize("config,pins", [
    ("qwen2-0.5b", dict(
        layer_matmul_params=QWEN2_LAYER,
        # one position: 24 layers, 14 heads of 64 at one pair, the head
        prefill_1=2 * 24 * QWEN2_LAYER + 24 * 4 * 14 * 64 + 2 * 896 * 151936,
        # 494M parameters, the published count of Qwen2-0.5B
        weight_count=494032768, kv_bytes_per_token=12288,
        decode_0=988065536, decode_1500=1006497536,
        prefill_384=281441370112, prefill_1536=1201050124288)),
    ("starcoder2-7b", dict(
        layer_matmul_params=STARCODER2_LAYER,
        prefill_1=2 * 8 * STARCODER2_LAYER + 8 * 4 * 36 * 128
        + 2 * 4608 * 49152,
        weight_count=2189812736, kv_bytes_per_token=16384,
        decode_0=3926640640, decode_1500=3951216640,
        prefill_384=1344940277760, prefill_1536=5508861788160)),
], ids=["qwen2-0.5b", "starcoder2-7b"])
def test_flop_counts_from_shapes(config, pins):
    arch, sp = load(config)
    assert arch.layer_matmul_params(sp) == pins["layer_matmul_params"]
    assert arch.prefill_flops(sp, 1) == pins["prefill_1"]
    assert arch.weight_count(sp) == pins["weight_count"]
    assert arch.kv_bytes_per_token(sp) == pins["kv_bytes_per_token"]
    assert arch.decode_bytes(sp, 0) == pins["decode_0"]
    assert arch.decode_bytes(sp, 1500) == pins["decode_1500"]
    assert arch.prefill_flops(sp, 384) == pins["prefill_384"]
    assert arch.prefill_flops(sp, 1536) == pins["prefill_1536"]
    assert flops.attention_flops(1, 1, 4) == 4 * 10
    assert flops.attention_flops(1, 1, 4, window=2) == 4 * 7
    assert flops.flash_cost(14, 2, 64, 512) == (470679552, 2097152)
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_read_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = harness.Tracer(True, 0.0, 1.0)
    tracer.poll(0.0)
    with harness.span("tick"):
        f(x).block_until_ready()
    tracer.stop()
    tr = tracer.read()
    assert any(s[0] == "bench.tick" for s in tr.spans)
    assert tr.window_s > 0
    assert tr.devices == {}          # the CPU has no device plane
