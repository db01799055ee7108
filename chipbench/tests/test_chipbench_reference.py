"""The plain reference against the program at a small size on CPU:
prefill-then-decode logits, the training loss, and the reference's own
blocked attention against an unblocked one."""
import dataclasses

import cb_fixtures
import numpy as np
import pytest

from chipbench import program
from chipbench import reference as R
from chipbench import weights as W

SEED = 2**35 + 11


@pytest.fixture(scope="module")
def setup():
    import jax.numpy as jnp

    cfg = cb_fixtures.TINY
    mc = dataclasses.replace(program.model_config(cfg), dtype="float32",
                             kv_cache_dtype="float32")
    flat = W.make(cfg, SEED)
    return cfg, mc, flat, program.to_tree(flat), jnp


def test_prefill_then_decode_logits_match(setup):
    import jax

    from repro.models import lm

    cfg, mc, flat, params, jnp = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], 37).tolist()
    n_dec = 6
    logits, cache = lm.prefill(params, mc, jnp.asarray([prompt]),
                               cache_len=64)
    got, toks = [np.asarray(logits[0])], []
    for i in range(n_dec):
        tok = int(np.argmax(got[-1]))
        toks.append(tok)
        logits, cache = lm.decode_step(params, mc, jnp.asarray([[tok]]),
                                       cache, jnp.int32(len(prompt) + i))
        got.append(np.asarray(logits[0]))
    seq = prompt + toks
    S = R.padded_len(len(seq))
    tokens = np.zeros(S, np.int32)
    tokens[:len(seq)] = seq
    idx = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.logits_at(flat, cfg, jnp.asarray(tokens),
                                      jnp.asarray(idx)))
    err = np.abs(np.stack(got) - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_train_loss_matches(setup):
    from repro.models import lm

    cfg, mc, flat, params, jnp = setup
    batch = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], (2, 512), dtype=np.int32)
    got, _ = lm.loss_fn(params, mc, {"tokens": jnp.asarray(batch)})
    want, grad = R.loss_and_grad(flat, cfg, batch)
    assert abs(float(got) - want) / want < 1e-5
    assert set(grad) == set(W.shapes(cfg))


def test_blocked_attention_equals_unblocked():
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    S, H, K, hd = 2 * R.QBLOCK, 4, 2, 8
    q = jax.random.normal(k[0], (S, H, hd))
    kk = jax.random.normal(k[1], (S, K, hd))
    v = jax.random.normal(k[2], (S, K, hd))
    got = R._attention(q, kk, v, "f32")
    kr, vr = jnp.repeat(kk, H // K, 1), jnp.repeat(v, H // K, 1)
    s = jnp.einsum("qhd,khd->hqk", q, kr, precision="highest") * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vr,
                      precision="highest")
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_weights_are_a_function_of_the_seed(setup):
    cfg = setup[0]
    a = W.make(cfg, SEED)
    b = W.make(cfg, SEED)
    c = W.make(cfg, SEED + 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert np.array_equal(W.leaf(cfg, SEED, "head"), a["head"])
