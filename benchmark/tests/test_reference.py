"""The plain reference against the program's model at tiny size on the CPU
(the same comparison the drivers make on the chip at the published widths)."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt2_ref
from ray_tpu.models import gpt2


def test_reference_agrees_with_the_program_in_float32():
    cfg = gpt2.GPT2Config.tiny(dtype=jnp.float32)
    params = gpt2.init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (4, 128)).astype(np.int32)
    y = rng.integers(0, cfg.vocab_size, (4, 128)).astype(np.int32)
    ours = gpt2_ref.logits(params, jnp.asarray(x), cfg.n_heads)
    theirs = gpt2.apply(params, x, cfg)
    assert np.abs(np.asarray(ours) - np.asarray(theirs)).max() < 1e-4
    loss = gpt2_ref.loss(params, jnp.asarray(x), jnp.asarray(y), cfg.n_heads)
    assert abs(loss - float(gpt2.loss_fn(
        params, {"inputs": x, "targets": y}, cfg))) < 1e-5


def test_bfloat16_program_stays_within_the_train_tolerance():
    cfg = gpt2.GPT2Config.tiny()  # bf16 compute, as the cells run
    params = gpt2.init(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(1)
    x = rng.integers(0, cfg.vocab_size, (4, 128)).astype(np.int32)
    y = rng.integers(0, cfg.vocab_size, (4, 128)).astype(np.int32)
    ref = gpt2_ref.loss(params, jnp.asarray(x), jnp.asarray(y), cfg.n_heads)
    got = float(gpt2.loss_fn(params, {"inputs": x, "targets": y}, cfg))
    assert 0 < abs(got - ref) < 0.02
