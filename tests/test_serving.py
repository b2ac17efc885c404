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
    done = eng.run_until_drained()
    assert len(done) == 7
    assert all(len(r.output) == 6 for r in done)
    assert eng.stats["prefills"] == 7
    # slots were recycled: more requests than slots
    assert eng.stats["ticks"] >= 2


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
    cfg = ModelConfig(name="h", family="hybrid", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=97,
                      attn_layer_period=4, attn_layer_offset=1,
                      ssm_state=16, ssm_head_dim=16, ssm_chunk=8, **F32)
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
