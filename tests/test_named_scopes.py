"""The name scopes the trace readers look for (``kv_insert``,
``attention``, ``attention_bwd``, ``optimizer``) are metadata only: the
lowered chunk and train steps carry them in their locations, and their
operations are exactly those lowered with every scope taken out."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.dist import split_tree
from repro.train import steps as T


def _has(text: str, scope: str) -> bool:
    """``scope`` as a whole component of some location's name path
    ("kv_insert/add", "jit(step)/optimizer/mul", "jvp(attention)/dot")."""
    return re.search(rf'["/(]{scope}[/)]', text) is not None


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("yi-9b").reduced()
    api = T.ModelAPI(cfg)
    params = jax.eval_shape(
        lambda: split_tree(api.init(cfg, jax.random.PRNGKey(0)))[0])
    return cfg, api, params


def _chunk(tiny):
    cfg, api, params = tiny
    B, C, page, max_pages = 2, 4, 4, 4
    cache = jax.eval_shape(lambda: api.init_paged_cache(B, B * max_pages,
                                                         page))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    return jax.jit(T.make_serve_chunk_step(cfg)).lower(
        params, i32(B, C), cache, i32(B, max_pages), i32(B), i32(B))


def _train(tiny):
    cfg, _, params = tiny
    opt = T.make_optimizer(cfg, total_steps=10)
    state = {"params": params, "opt": jax.eval_shape(opt.init, params)}
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
    return jax.jit(T.make_train_step(cfg, opt)).lower(state, batch)


@pytest.mark.parametrize("step,scopes", [
    (_chunk, ("kv_insert", "attention")),
    (_train, ("attention", "attention_bwd", "optimizer")),
])
def test_scopes_are_metadata_only(tiny, monkeypatch, step, scopes):
    # the Pallas kernels (interpret mode), so that flash attention's own
    # backward rule is what the train step differentiates through
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    named = step(tiny)
    text = named.as_text(debug_info=True)
    for scope in scopes:
        assert _has(text, scope), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = step(tiny)
    for scope in scopes:
        assert not _has(bare.as_text(debug_info=True), scope), scope
    assert named.as_text() == bare.as_text()
