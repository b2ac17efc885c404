"""Serving engine: continuous batching, greedy agreement with the full
forward, slot recycling, temperature sampling."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import apply_model, init_model
from repro.serving import Request, ServeConfig, ServingEngine

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, q_block=8)
HYBRID = dict(name="h", family="hybrid", n_layers=4, d_model=64, n_heads=4,
              n_kv_heads=2, d_ff=128, vocab=97, attn_layer_period=4,
              attn_layer_offset=1, ssm_state=16, ssm_head_dim=16,
              ssm_chunk=8, **F32)


def make(cfg):
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return params


@pytest.fixture(scope="module")
def dense_setup():
    cfg = ModelConfig(name="d", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab=211, **F32)
    return cfg, make(cfg)


def test_continuous_batching_drains(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=3, max_seq=64,
                                                 max_new_tokens=6))
    for i in range(7):
        eng.submit(Request(rid=i,
                           prompt=np.arange(4 + i % 3, dtype=np.int32)))
    eng.tick()
    donated = jax.tree.leaves(eng.caches)
    done = eng.run_until_drained()
    assert len(done) == 7
    assert all(len(r.output) == 6 for r in done)
    assert eng.stats["prefills"] == 7
    # slots were recycled: more requests than slots
    assert eng.stats["ticks"] >= 2
    # the decode consumed the cache it was given; the engine's own stays
    # usable: a request admitted into a freed slot after the drain gets
    # the tokens the same prompt got before
    assert all(x.is_deleted() for x in donated)
    first = next(r for r in done if r.rid == 0)
    eng.submit(Request(rid=7, prompt=first.prompt.copy()))
    assert eng.run_until_drained()[-1].output == first.output


def test_greedy_matches_full_forward(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=2, max_seq=64,
                                                 max_new_tokens=5))
    eng.submit(Request(rid=0, prompt=np.arange(7, dtype=np.int32)))
    done = eng.run_until_drained()
    r = done[0]
    toks = list(r.prompt)
    for _ in range(len(r.output)):
        lg, _ = apply_model(cfg, params,
                            jnp.asarray(toks, jnp.int32)[None])
        toks.append(int(jnp.argmax(lg[0, -1])))
    assert toks[len(r.prompt):] == r.output


def test_hybrid_serving_greedy():
    cfg = ModelConfig(**HYBRID)
    params = make(cfg)
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=2, max_seq=64,
                                                 max_new_tokens=4))
    eng.submit(Request(rid=0, prompt=np.arange(6, dtype=np.int32)))
    done = eng.run_until_drained()
    r = done[0]
    toks = list(r.prompt)
    for _ in range(len(r.output)):
        lg, _ = apply_model(cfg, params,
                            jnp.asarray(toks, jnp.int32)[None])
        toks.append(int(jnp.argmax(lg[0, -1])))
    assert toks[len(r.prompt):] == r.output


def _decode_families():
    from repro.configs.base import get_smoke_config
    return {
        "dense": ModelConfig(name="d", n_layers=2, d_model=64, n_heads=4,
                             n_kv_heads=2, d_ff=128, vocab=211, **F32),
        "sliding_window": get_smoke_config("starcoder2-7b"),
        "mla": get_smoke_config("deepseek-v3-671b"),
        "hybrid": ModelConfig(**HYBRID),
    }


@pytest.mark.parametrize("family", ["dense", "sliding_window", "mla",
                                    "hybrid"])
def test_decode_slots_matches_per_slot_decode(family):
    """The engine's one-batch decode with a length per slot gives every
    slot the logits and cache that ``decode_step`` with that slot's
    scalar length gives it alone: slots at 0, at max_seq - 2 and on
    both sides of a block edge of the row write, and a free slot (length
    0, stale cache)."""
    from repro.models import decode_step
    from repro.models.model import ROW_BLOCK, cache_batch_axes
    cfg = _decode_families()[family]
    params = make(cfg)
    max_seq = 2 * ROW_BLOCK
    lengths = np.array([0, max_seq - 2, ROW_BLOCK - 1, ROW_BLOCK, 0],
                       np.int32)
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=len(lengths),
                                                 max_seq=max_seq),
                        use_executor=False)
    leaves, tree = jax.tree.flatten(eng.caches)
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    caches = tree.unflatten([jax.random.normal(k, x.shape, x.dtype)
                             for k, x in zip(keys, leaves)])
    axes = cache_batch_axes(cfg, caches)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (len(lengths), 1),
                                0, cfg.vocab)
    want = []
    for b, n in enumerate(lengths):
        one = jax.tree.map(lambda t, a: jnp.expand_dims(jnp.take(t, b, a), a),
                           caches, axes)
        want.append(decode_step(cfg, params, tokens[b:b + 1], one,
                                jnp.int32(n)))
    lg, got = eng._decode(params, tokens, caches, jnp.asarray(lengths))
    for b, (lg_b, cache_b) in enumerate(want):
        np.testing.assert_allclose(lg[b], lg_b[0], rtol=1e-5, atol=1e-5)
        jax.tree.map(lambda g, w, a: np.testing.assert_allclose(
            jnp.take(g, b, a), jnp.take(w, 0, a), rtol=1e-5, atol=1e-5),
            got, cache_b, axes)


def test_decode_slots_donates_and_moves_no_cache_sized_layout():
    """The engine's decode donates every cache leaf, and no transpose,
    broadcast or squeeze of its program touches an array as large as one
    layer's K cache (the tied head's embedding transpose aside)."""
    from repro.serving.engine import make_decode_fn
    cfg = ModelConfig(name="d", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab=211,
                      tie_embeddings=True, **F32)
    params = make(cfg)
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=4, max_seq=64),
                        use_executor=False)
    tokens = jnp.zeros((4, 1), jnp.int32)
    lengths = jnp.zeros((4,), jnp.int32)
    text = eng._decode.lower(params, tokens, eng.caches, lengths).as_text()
    n_cache = len(jax.tree.leaves(eng.caches))
    assert text.count("tf.aliasing_output") \
        + text.count("jax.buffer_donor") == n_cache
    slab = 4 * 64 * cfg.n_kv_heads * cfg.head_dim
    emb = params["embed"]["emb"].shape
    jaxpr = jax.make_jaxpr(make_decode_fn(cfg))(params, tokens, eng.caches,
                                                 lengths)

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for v in e.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from eqns(sub)

    moved = [(e.primitive.name, [v.aval.shape for v in e.invars])
             for e in eqns(jaxpr.jaxpr)
             if e.primitive.name in ("transpose", "broadcast_in_dim",
                                     "squeeze")
             and max(np.prod(v.aval.shape) for v in e.invars + e.outvars
                     if hasattr(v.aval, "shape")) >= slab
             and not (e.primitive.name == "transpose"
                      and e.invars[0].aval.shape == emb)]
    assert moved == []


def test_eos_terminates(dense_setup):
    cfg, params = dense_setup
    # find the greedy first token and use it as EOS: request stops at 1
    eng0 = ServingEngine(cfg, params, ServeConfig(n_slots=1, max_seq=64,
                                                  max_new_tokens=3))
    eng0.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32)))
    first = eng0.run_until_drained()[0].output[0]

    eng = ServingEngine(cfg, params, ServeConfig(n_slots=1, max_seq=64,
                                                 max_new_tokens=50,
                                                 eos_token=first))
    eng.submit(Request(rid=1, prompt=np.arange(5, dtype=np.int32)))
    done = eng.run_until_drained()
    assert done[0].output == [first]


def test_per_request_max_new(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=2, max_seq=64,
                                                 max_new_tokens=10))
    eng.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2))
    done = eng.run_until_drained()
    assert len(done[0].output) == 2


def test_oversized_prompt_rejected(dense_setup):
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=1, max_seq=16))
    eng.submit(Request(rid=0, prompt=np.arange(20, dtype=np.int32)))
    done = eng.run_until_drained()
    assert done[0].done and done[0].output == []


def test_temperature_sampling_varies(dense_setup):
    cfg, params = dense_setup
    outs = set()
    for seed in range(3):
        eng = ServingEngine(cfg, params, ServeConfig(
            n_slots=1, max_seq=64, max_new_tokens=8, temperature=1.5,
            seed=seed))
        eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32)))
        outs.add(tuple(eng.run_until_drained()[0].output))
    assert len(outs) > 1


def _run_serve_cli(tmp_path, patch: str = ""):
    """``python -m repro.launch.serve`` on the smoke config in a child,
    with ``patch`` run first; returns the finished process."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = (f"import sys\n{patch}\nfrom repro.launch import serve\n"
            "sys.exit(serve.main(['--arch', 'qwen2-0.5b', '--requests', "
            "'3', '--max-new', '3']))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.path.abspath(src))
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)


def test_serve_cli_exits_zero_when_every_request_served(tmp_path):
    out = _run_serve_cli(tmp_path)
    assert out.returncode == 0, out.stderr
    assert "served 3 requests, 9 tokens" in out.stdout


def test_serve_cli_exits_nonzero_when_prefill_evicted(tmp_path):
    # every prefill raises: the engine evicts the requests, and the
    # entry point must say so and fail instead of reporting success
    out = _run_serve_cli(tmp_path, patch=(
        "from repro.serving import ServingEngine\n"
        "def broken(self, plen):\n"
        "    def prefill(*args):\n"
        "        raise RuntimeError('injected prefill failure')\n"
        "    return prefill\n"
        "ServingEngine.prefill_program = broken\n"))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "evicted: prefill failed" in out.stderr
    assert "injected prefill failure" in out.stderr


# -- spans and counters -------------------------------------------------------
SPANS = ("serve.tick", "amt.run", "amt.task", "serve.admit", "serve.prefill",
         "serve.slot_write", "serve.sync", "serve.decode")


@pytest.fixture(scope="module")
def traced(dense_setup, tmp_path_factory):
    """One engine drained under the profiler: (engine, spans), each span
    (name, start ns, end ns, args) from the host plane."""
    import glob
    from jax.profiler import ProfileData
    cfg, params = dense_setup
    eng = ServingEngine(cfg, params, ServeConfig(n_slots=3, max_seq=64,
                                                 max_new_tokens=6))
    reqs = [Request(rid=i, prompt=np.arange(4 + i % 4, dtype=np.int32),
                    max_new_tokens=1 + i % 5) for i in range(8)]
    for r in reqs:
        eng.submit(r)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        eng.run_until_drained()
    pd = ProfileData.from_file(glob.glob(f"{d}/**/*.xplane.pb",
                                         recursive=True)[0])
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in SPANS]
    return eng, spans


def _named(spans, span, **args):
    return [s for s in spans if s[0] == span and
            all(s[3].get(k) == v for k, v in args.items())]


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_every_span_is_traced(traced):
    _, spans = traced
    assert {s[0] for s in spans} == set(SPANS)
    assert all(set(s[3]) == {"queued"} for s in _named(spans, "serve.tick"))


def test_spans_nest_in_their_cause(traced):
    _, spans = traced
    ticks, runs = _named(spans, "serve.tick"), _named(spans, "amt.run")
    for admit in _named(spans, "serve.admit"):
        # the admission task of the same request holds it
        task = _named(spans, "amt.task", name=f"prefill:{admit[3]['rid']}")
        assert _inside(admit, task)
        assert _inside(admit, runs) and _inside(admit, ticks)
        assert set(admit[3]) == {"rid", "plen", "slot"}
    admits = _named(spans, "serve.admit")
    for name in ("serve.prefill", "serve.slot_write"):
        assert all(_inside(s, admits) for s in _named(spans, name))
    assert all(_inside(s, admits)
               for s in _named(spans, "serve.sync", what="first"))
    decodes = _named(spans, "serve.decode")
    assert all(_inside(d, _named(spans, "amt.task", name="decode"))
               for d in decodes)
    assert all(_inside(s, decodes)
               for s in _named(spans, "serve.sync", what="decode"))
    assert all(_inside(t, runs) for t in _named(spans, "amt.task"))
    assert all(_inside(r, ticks) for r in runs)


def test_span_counts_match_the_engine_counters(traced):
    eng, spans = traced
    assert len(_named(spans, "serve.admit")) == eng.stats["prefills"] == 8
    assert len(_named(spans, "serve.decode")) == eng.stats["ticks"]
    assert len(_named(spans, "serve.sync")) == \
        eng.stats["prefills"] + eng.stats["ticks"]
    # amt.run carries the size of the graph it drains
    last = max(_named(spans, "amt.run"), key=lambda r: r[1])
    assert last[3]["tasks"] == len(eng._executor.graph)

