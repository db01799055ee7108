"""The engine serves from a compute-dtype copy of its weights, made once
(``optim.precision.serving_copy``): its programs give bit-identical
logits and tokens to the same programs fed the float32 master tree; the
leaves read only through a cast to bfloat16 are cast, every leaf read in
float32 is not; ``ServeReport.weights_cast_share`` counts the copy; and
a fleet's replicas share one copy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist import split_tree
from repro.optim.precision import serving_copy
from repro.serve import Engine, ServeConfig, run_offline
from repro.serve.engine import synthetic_requests
from repro.train.steps import ModelAPI

# name -> (layout, leaves cast to bfloat16, leaves kept in float32), by
# the tail of each leaf's path
CASES = {
    "yi-9b": ("paged",
              ["['wq']", "['wk']", "['wv']", "['wo']", "['wg']", "['wu']",
               "['wd']", "['embed']", "['head']"],
              ["['scale']"]),
    "trinity-mini": ("paged",
                     ["['wq']", "['wk']", "['wv']", "['wo']", "['wgate']",
                      "['wg']", "['wu']", "['wd']", "['head']"],
                     ["['router']", "['bias']", "['scale']"]),
    "rwkv6-3b": ("slab",
                 ["['ffn']['wu']", "['ffn']['wd']", "['head']"],
                 ["['mixer']['wr']", "['mixer']['wk']", "['mixer']['wv']",
                  "['mixer']['wo']", "['mixer']['wg']", "['mixer']['w1']",
                  "['mixer']['w2']", "['scale']"]),
    "jamba-1.5-large-398b": ("slab",
                             ["['wx']", "['wz']", "['out_proj']", "['wq']",
                              "['wo']", "['wd']", "['head']"],
                             ["['x_proj']", "['dt_w']", "['router']",
                              "['A_log']", "['D']", "['scale']"]),
    "whisper-medium": ("paged",
                       ["['self_attn']['wq']", "['cross_attn']['wk']",
                        "['attn']['wv']", "['ffn']['wu']", "['head']"],
                       ["['scale']", "['norm1']['bias']"]),
}


def _setup(name):
    cfg = get_config(name).reduced()
    master, _ = split_tree(ModelAPI(cfg).init(cfg, jax.random.PRNGKey(0)))
    scfg = ServeConfig(max_batch=2, max_len=24, prefill_len=12,
                       page_size=4, prefill_chunk=4)
    return cfg, master, scfg


def _requests(cfg):
    return synthetic_requests(cfg, n=3, tokens=4, prompt_len=12, seed=3)


def _recording(eng):
    """Wrap the engine's programs so each call's logits are kept."""
    seen = []
    for attr in ("_chunk_jit", "_prefill_jit", "_decode_jit"):
        fn = getattr(eng, attr, None)
        if fn is None:
            continue

        def rec(*args, _fn=fn):
            out = _fn(*args)
            seen.append(np.asarray(out[0]))
            return out
        setattr(eng, attr, rec)
    return seen


@pytest.mark.parametrize("name", sorted(CASES))
def test_copy_serves_bit_identical_logits_and_tokens(name):
    cfg, master, scfg = _setup(name)
    layout, cast, kept = CASES[name]
    eng = Engine(cfg, master, None, scfg)
    assert eng.layout == layout
    dtypes = {jax.tree_util.keystr(p): w.dtype for p, w in
              jax.tree_util.tree_leaves_with_path(eng.params)}
    for tail, want in [(t, jnp.bfloat16) for t in cast] + [
            (t, jnp.float32) for t in kept]:
        hits = [k for k in dtypes if k.endswith(tail)]
        assert hits, tail
        assert all(dtypes[k] == want for k in hits), (tail, want, hits)
    for w in jax.tree_util.tree_leaves(eng.params):
        assert w.ndim >= 2 or w.dtype == jnp.float32

    ref = Engine(cfg, master, None, scfg)
    ref.params = master  # the same programs, fed the float32 masters
    got, want = _recording(eng), _recording(ref)
    served = run_offline(eng, _requests(cfg))
    served_ref = run_offline(ref, _requests(cfg))
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    tokens = {r.id - served.requests[0].id: r.tokens
              for r in served.requests}
    tokens_ref = {r.id - served_ref.requests[0].id: r.tokens
                  for r in served_ref.requests}
    assert tokens == tokens_ref
    assert sum(len(t) for t in tokens.values()) == 12


def test_weights_cast_share_counts_the_copy():
    """Dense Yi: everything but the norm scales is served in bfloat16."""
    cfg, master, scfg = _setup("yi-9b")
    total = sum(w.size for w in jax.tree_util.tree_leaves(master))
    norms = (2 * cfg.n_layers + 1) * cfg.d_model
    eng = Engine(cfg, master, None, scfg)
    report = run_offline(eng, _requests(cfg))
    assert report.weights_cast_share == pytest.approx(
        100.0 * (total - norms) / total)


def test_serving_copy_is_conservative():
    """A leaf is cast only where every use, in every program, is a cast
    to bfloat16, followed into scan, loop, remat and custom-derivative
    bodies: not where another
    program reads it in float32, where it is cast to float16, reshaped
    first, read in float32 beside the cast, or never read."""
    f32 = jnp.float32
    p = {k: jnp.ones((4, 4), f32) for k in
         ("both", "half", "stacked", "looped", "unused", "mixed_loop")}
    p["vec"] = jnp.ones((4,), f32)
    p["scanned"] = jnp.ones((2, 4, 4), f32)
    p["remat"] = jnp.ones((4, 4), f32)
    p["custom"] = jnp.ones((4, 4), f32)

    @jax.custom_vjp
    def custom(x, w):
        return x @ w.astype(jnp.bfloat16)
    custom.defvjp(lambda x, w: (custom(x, w), None),
                  lambda _, g: (g, None))

    def one(p, x):
        bf = jnp.bfloat16
        y = x @ p["both"].astype(bf) + x @ p["half"].astype(jnp.float16)
        y = y + x @ p["vec"].astype(bf)[:, None]
        y, _ = jax.lax.scan(lambda c, w: (c @ w.astype(bf), None), y,
                            p["scanned"])
        y, _ = jax.lax.scan(lambda c, w: (c @ w.astype(bf), None), y,
                            p["stacked"][None])  # a reshape reads it
        y = jax.lax.fori_loop(0, 2, lambda i, c: c @ p["looped"].astype(bf),
                              y)
        y = jax.checkpoint(lambda y, w: y @ w.astype(bf))(y, p["remat"])
        y = custom(y, p["custom"])
        y = jax.lax.fori_loop(
            0, 2, lambda i, c: c @ p["mixed_loop"].astype(bf)
            + (c.astype(f32) @ p["mixed_loop"]).astype(bf), y)
        return y

    def two(p, x):
        return x @ p["both"]

    x = jax.ShapeDtypeStruct((2, 4), jnp.bfloat16)
    programs = [(one, (x,)), (two, (x.update(dtype=f32),))]
    out = serving_copy(p, programs)
    dt = {k: v.dtype for k, v in out.items()}
    assert dt == {"both": f32, "half": f32, "scanned": jnp.bfloat16,
                  "stacked": f32, "looped": jnp.bfloat16, "unused": f32,
                  "mixed_loop": f32, "vec": f32, "remat": jnp.bfloat16,
                  "custom": jnp.bfloat16}
    assert out["both"] is p["both"]
    again = serving_copy(out, programs)  # a copy passes through whole
    assert all(again[k] is out[k] for k in out)


def test_fleet_replicas_share_one_copy():
    from repro.run import RunSpec, run_spec
    from repro.run.overrides import apply_assignments

    spec = apply_assignments(RunSpec(arch="yi-9b", mode="serve"), [
        "fleet.n_replicas=2", "serve.tokens=2", "serve.batch=2",
        "serve.prompt_len=8", "serve.warmup=false"])
    fleet = run_spec(spec)["fleet"]
    a, b = (jax.tree_util.tree_leaves(r.engine.params)
            for r in fleet.replicas.values())
    assert len(a) == len(b) and all(x is y for x, y in zip(a, b))
    assert any(x.dtype == jnp.bfloat16 for x in a)
