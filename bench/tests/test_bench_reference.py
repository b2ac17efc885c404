"""The float32 reference against the program's own forward pass at a
small size, and the control (float8 matmul operands) against both.  Each
configuration's reference is that of the architecture its ``"arch"``
names."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import serving
from bench.tests import tiny

WORKLOADS = [("qwen2-0.5b", "chat"), ("starcoder2-7b", "code")]


def small(workload, **over):
    """(arch, spec, config) of the cell cut small, with ``over`` set."""
    cell = tiny.serving_cell(*workload)
    c = dict(cell.config, **over)
    arch, spec = serving.load_arch(c, cell.config_file)
    return arch, spec, c


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("window", [None, 16])
def test_reference_agrees_with_apply_model(workload, window):
    from repro.models import apply_model
    over = {"torch_dtype": "float32"}
    if window is not None:
        over.update(sliding_window=window, use_sliding_window=True)
    arch, spec, c = small(workload, **over)
    w = arch.make_weights(spec, 2**33 + 3)
    cfg = arch.program_config(spec, c["serve"])
    params = arch.program_params(spec, w)
    serving.check_layout(cfg, params)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, spec.vocab_size,
                                                         48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(apply_model(cfg, params, toks[None])[0][0])
    got = np.asarray(arch.logits(spec, w, toks))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())


def test_gap_is_zero_for_the_reference_own_choice():
    arch, spec, _ = small(("qwen2-0.5b", "chat"), torch_dtype="float32")
    w = arch.make_weights(spec, 1)
    toks = jnp.arange(32, dtype=jnp.int32)
    best = jnp.argmax(arch.logits(spec, w, toks), -1).astype(jnp.int32)
    g = np.asarray(arch.gaps(spec, w, toks, best))
    assert (g == 0).all()
    worst = jnp.argmin(arch.logits(spec, w, toks), -1).astype(jnp.int32)
    assert (np.asarray(arch.gaps(spec, w, toks, worst)) > 0).all()


def test_weights_depend_on_all_64_bits_of_the_seed():
    arch, spec, _ = small(("qwen2-0.5b", "chat"))
    a = arch.make_weights(spec, 5)["wq"]
    b = arch.make_weights(spec, 5 + 2**33)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert a.dtype == jnp.bfloat16


def test_fp8_control_moves_far_from_the_reference():
    """The control's widest gap over a few hundred positions is many
    times what rounding the reference's inputs to bfloat16 gives."""
    arch, spec, _ = small(("qwen2-0.5b", "chat"), torch_dtype="float32")
    w = arch.make_weights(spec, 11)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, spec.vocab_size,
                                                         256), jnp.int32)
    ctrl = float(np.asarray(arch.gaps(spec, w, toks, toks,
                                          fp8=True)).max())
    wb = {k: v.astype(jnp.bfloat16).astype(jnp.float32) for k, v in w.items()}
    best_b = jnp.argmax(arch.logits(spec, wb, toks), -1).astype(jnp.int32)
    bf16 = float(np.asarray(arch.gaps(spec, w, toks, best_b)).max())
    assert ctrl > 3 * bf16 and ctrl > 0


def digest(named_arrays) -> str:
    h = hashlib.sha256()
    for name, a in named_arrays:
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_weights_and_logits_are_pinned_bit_for_bit():
    """The weights and the reference's logits of the small qwen2 cell at
    one seed hash to what ``bench/modelref.py`` gave before the
    reference moved into ``bench/archs/gqa.py``."""
    arch, spec, _ = small(("qwen2-0.5b", "chat"))
    w = arch.make_weights(spec, 2**40 + 16)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, spec.vocab_size,
                                                         48), jnp.int32)
    assert digest(sorted(w.items())) == \
        "4c3b25f1c66d4cfa05f704a03298d5657e06f31bf0ae0e53148030025ba06ef5"
    assert digest([("logits", arch.logits(spec, w, toks))]) == \
        "59a6ba700c3f299afecb3b4554469458d2642aa0515f86e90d232c667d1f08fb"
