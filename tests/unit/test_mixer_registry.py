"""The seam of the model layer: a mixer is ONE record in
``models/transformer.MIXERS`` and a step's operands are ONE record, ``Step``.

What holds the seam in place: seeded parameters are the bits they were before
the mixers moved behind the table (digests recorded from the parent of PR 50,
leaf by leaf); a FOURTH mixer that only this file knows goes through init,
axes, the cache's shapes and ``forward`` with and without pages; the state
pools of the two recurrent families have the shapes they had; and no module
but the model's own names a mixer by its string.
"""

import ast
import dataclasses
import hashlib
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import kv_cache
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.core import EMBED, LAYERS
from deepspeed_tpu.models.presets import transformer_config

REPO = pathlib.Path(__file__).resolve().parents[2]

# sha256 (first ten hex digits) of every leaf of
# ``init_params(PRNGKey(0), transformer_config(preset))`` at c6e4150, the
# parent of PR 50, on the CPU
PARENT_DIGESTS = {
    "tiny-opt": """
        embed/tokens=6865c2299c final_norm/bias=5341e6b264
        final_norm/scale=2f20cd03c9 layers/attn/bk=076a27c79e
        layers/attn/bo=076a27c79e layers/attn/bq=076a27c79e
        layers/attn/bv=076a27c79e layers/attn/wk=6340c995c5
        layers/attn/wo=881bdeb2da layers/attn/wq=6f783c6a6d
        layers/attn/wv=911a95ba58 layers/ln1/bias=076a27c79e
        layers/ln1/scale=02722f124d layers/ln2/bias=076a27c79e
        layers/ln2/scale=02722f124d layers/mlp/b_down=076a27c79e
        layers/mlp/b_up=e5a00aa999 layers/mlp/w_down=c5642ca900
        layers/mlp/w_up=91702d329a pos=53e217c7b8""",
    "tiny-olmoe": """
        embed/tokens=6865c2299c final_norm/scale=2f20cd03c9
        layers/attn/k_norm=02722f124d layers/attn/q_norm=02722f124d
        layers/attn/wk=6340c995c5 layers/attn/wo=881bdeb2da
        layers/attn/wq=6f783c6a6d layers/attn/wv=911a95ba58
        layers/ln1/scale=02722f124d layers/ln2/scale=02722f124d
        layers/mlp/w_down=c5642ca900 layers/mlp/w_gate=94a743eebd
        layers/mlp/w_up=91702d329a layers/router=6a593738bf lm_head=1d87c77b8f""",
    "tiny-solar-open2": """
        embed/tokens=6865c2299c final_norm/scale=2f20cd03c9
        layers/attn/attn/wg=6237b138fe layers/attn/attn/wk=2c32ac0590
        layers/attn/attn/wo=06b13a1285 layers/attn/attn/wq=1ea8f17f66
        layers/attn/attn/wv=8d44007806 layers/attn/ln1/scale=2f20cd03c9
        layers/attn/ln2/scale=2f20cd03c9 layers/attn/mlp/w_down=e066b11b56
        layers/attn/mlp/w_gate=baa87d29bf layers/attn/mlp/w_up=ed2f6666cc
        layers/attn/router=ebe37184e1 layers/attn/router_bias=ea0bed1618
        layers/attn/shared/w_down=036b6f8641
        layers/attn/shared/w_gate=ec714f94bb layers/attn/shared/w_up=db4bfee33f
        layers/kda/kda/A_log=db9d3f98a0 layers/kda/kda/conv_k=5e3b576f54
        layers/kda/kda/conv_q=85757947d8 layers/kda/kda/conv_v=ecc724e9d7
        layers/kda/kda/dt_bias=4a1b68da0c layers/kda/kda/o_norm=c90489868a
        layers/kda/kda/wb=7a4ff6ef78 layers/kda/kda/wf1=854af12258
        layers/kda/kda/wf2=ecc2b067b2 layers/kda/kda/wg1=495b81a1ca
        layers/kda/kda/wg2=fadde636ff layers/kda/kda/wk=8561f32d89
        layers/kda/kda/wo=25d10467a1 layers/kda/kda/wq=94b46ce622
        layers/kda/kda/wv=cfd102d31e layers/kda/ln1/scale=5ce183e97a
        layers/kda/ln2/scale=5ce183e97a layers/kda/mlp/w_down=973eb37902
        layers/kda/mlp/w_gate=c32f6168f6 layers/kda/mlp/w_up=9b516a69b2
        layers/kda/router=30af6119cd layers/kda/router_bias=405490c036
        layers/kda/shared/w_down=06591e0759 layers/kda/shared/w_gate=19f74c8b92
        layers/kda/shared/w_up=8f9891c345 lm_head=1d87c77b8f""",
    "tiny-nemotron-3-super": """
        embed/tokens=6865c2299c final_norm/scale=2f20cd03c9
        layers/attn_mixer/attn/wk=b7a1ee4c7a
        layers/attn_mixer/attn/wo=01ed78a5cf
        layers/attn_mixer/attn/wq=bdf02a7db3
        layers/attn_mixer/attn/wv=4ffa1b3a6d
        layers/attn_mixer/ln1/scale=2f20cd03c9
        layers/ffn/latent/w_in=9d0004ebaa layers/ffn/latent/w_out=3b2f2d7340
        layers/ffn/ln2/scale=5c9d51f4ee layers/ffn/mlp/w_down=4624d0d584
        layers/ffn/mlp/w_up=7c1c62beb4 layers/ffn/router=9e384e478b
        layers/ffn/router_bias=dc35111d89 layers/ffn/shared/w_down=2dbe7990c4
        layers/ffn/shared/w_up=fb7f926050
        layers/mamba2_mixer/ln1/scale=5c9d51f4ee
        layers/mamba2_mixer/mamba2/A_log=f9007004d5
        layers/mamba2_mixer/mamba2/D=a834ffb029
        layers/mamba2_mixer/mamba2/conv_b=a669d378d0
        layers/mamba2_mixer/mamba2/conv_w=f6d9eff487
        layers/mamba2_mixer/mamba2/dt_bias=d7f514a79c
        layers/mamba2_mixer/mamba2/norm=71f3f0e945
        layers/mamba2_mixer/mamba2/w_in=e7edfa5033
        layers/mamba2_mixer/mamba2/w_out=fd5410a44f lm_head=1d87c77b8f""",
}


@pytest.mark.parametrize("preset", sorted(PARENT_DIGESTS))
def test_seeded_parameters_are_the_parents_bits(preset):
    """The harness's reference compares on seeded weights: a mixer's
    ``fold_in`` tags, shapes and dtypes are its own for good."""
    params = T.init_params(jax.random.PRNGKey(0), transformer_config(preset))
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:10]
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    want = dict(item.split("=") for item in PARENT_DIGESTS[preset].split())
    assert sorted(got) == sorted(want)
    assert {k: v for k, v in got.items() if v != want[k]} == {}


# ---------------------------------------------------------------------------
# a fourth mixer, registered here alone: a running mean a slot
# ---------------------------------------------------------------------------


def _mean_mixer(cfg, h, p, step):
    """``out[t] = mean(h[:t + 1]) W``. Its state is the running SUM
    (1, H) and the count is the position; a token that does not exist adds
    nothing; a row at position 0 starts from zeros whatever its slot held."""
    real, cache = step.write_mask, step.cache
    hs = h.astype(jnp.float32)
    if real is not None:
        hs = jnp.where(real[..., None], hs, 0.0)
    pos = step.positions if step.positions.ndim == 2 else step.positions[None]
    start, new_cache = 0.0, None
    if cache is not None:
        at = (step.layer_index, step.state_slots)
        start = jnp.where((pos[:, 0] == 0)[:, None, None], 0.0,
                          cache["state"][at])
    sums = start + jnp.cumsum(hs, axis=1)
    if cache is not None:
        new_cache = {**cache, "state": cache["state"].at[at].set(sums[:, -1:])}
    mean = sums / (jnp.maximum(pos, 0) + 1)[..., None]
    return jnp.einsum("bsh,hd->bsd", mean.astype(h.dtype), p["w"]), new_cache


MEAN = T.Mixer(
    name="mean",
    init=lambda cfg, normal, uniform: {
        "w": normal(90, (cfg.hidden_size, cfg.hidden_size))},
    axes=lambda cfg: {"w": (LAYERS, EMBED, None)},
    apply=_mean_mixer,
    state=lambda cfg: ((1, cfg.hidden_size), 2, cfg.hidden_size),
    rows_count="mean_rows")


@pytest.fixture
def mean_model(monkeypatch):
    """tiny-llama with every second layer's attention replaced by the running
    mean: ("attn", "mean") twice. Nothing of the package is edited: the
    mixer and its kind of layer are two table entries."""
    monkeypatch.setitem(T.MIXERS, "mean", MEAN)
    monkeypatch.setitem(T.LAYER_KINDS, "mean", ("mean", True))
    cfg = transformer_config("tiny-llama", num_layers=4,
                             layer_pattern=("attn", "mean"))
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_a_fourth_mixer_has_params_axes_and_state_pools(mean_model):
    cfg, params = mean_model
    H = cfg.hidden_size
    assert T.recurrent_layers(cfg) == ("mean", (1, 3))
    assert T.layers_with_mixer(cfg, "attn") == (0, 2)
    layer = params["layers"]["mean"]
    assert sorted(layer) == ["ln1", "ln2", "mean", "mlp"]
    assert layer["mean"]["w"].shape == (2, H, H)
    axes = T.param_axes(cfg)
    assert axes["layers"]["mean"]["mean"] == {"w": (LAYERS, EMBED, None)}
    is_axes = lambda x: isinstance(x, tuple)       # noqa: E731
    assert (jax.tree.structure(axes, is_leaf=is_axes)
            == jax.tree.structure(params))
    for a, leaf in zip(jax.tree.leaves(axes, is_leaf=is_axes),
                       jax.tree.leaves(params)):
        assert len(a) == leaf.ndim
    kv = cfg.num_kv_heads * cfg.head_dim
    shapes = {"k": (2, 9, 4, kv), "v": (2, 9, 4, kv),
              "state": (2, 3, 1, H), "tail": (2, 3, 1, H)}
    cache = kv_cache.init_paged_cache(cfg, 9, 4, jnp.float32, state_slots=3)
    struct = kv_cache.paged_cache_shape_struct(cfg, 9, 4, jnp.float32,
                                               state_slots=3)
    assert {k: v.shape for k, v in cache.items()} == shapes
    assert {k: v.shape for k, v in struct.items()} == shapes
    assert cache["state"].dtype == jnp.float32
    with pytest.raises(ValueError, match="state_slots"):
        kv_cache.init_paged_cache(cfg, 9, 4, jnp.float32)


def test_a_fourth_mixer_runs_forward_with_and_without_pages(mean_model):
    """The whole sequence with no cache, against the same sequence through
    the pages and the state pools: a ragged chunk of 5 (padded to 8), then a
    token a step. The slot holds another sequence's state beforehand."""
    cfg, params = mean_model
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 9), 0,
                             cfg.vocab_size)
    want, no_cache, _ = T.forward(params, ids, cfg)
    assert no_cache is None and np.isfinite(np.asarray(want)).all()
    # the mean mixer is in the program: without its weights the logits move
    zeroed = jax.tree.map(lambda a: a, params)
    zeroed["layers"]["mean"]["mean"]["w"] *= 0
    assert np.abs(np.asarray(T.forward(zeroed, ids, cfg)[0] - want)).max() \
        > 1e-3

    cache = kv_cache.init_paged_cache(cfg, 9, 4, jnp.float32, state_slots=3)
    cache["state"] = cache["state"] + 7.0          # stale: the slot is reused
    table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    slots = jnp.asarray([1], jnp.int32)

    def run(cache, tokens, pos, mask, **how):
        logits, cache, _ = T.forward(
            params, tokens, cfg, cache=cache, positions=pos,
            block_table=table, paged_write_mask=mask, state_slots=slots,
            **how)
        return np.asarray(logits), cache

    chunk = jnp.zeros((1, 8), jnp.int32).at[0, :5].set(ids[0, :5])
    mask = jnp.arange(8)[None] < 5
    got, cache = run(cache, chunk, jnp.arange(8)[None], mask,
                     paged_run=(jnp.int32(0), jnp.int32(5)))
    out = [got[0, :5]]
    for p in range(5, 9):
        got, cache = run(cache, ids[:, p:p + 1], jnp.asarray([[p]]),
                         jnp.ones((1, 1), bool))
        out.append(got[0])
    np.testing.assert_allclose(np.concatenate(out), np.asarray(want)[0],
                               atol=2e-5)
    # the other slots were not touched
    assert float(jnp.abs(cache["state"][:, 0] - 7.0).max()) == 0.0


@pytest.mark.parametrize("preset,shapes", [
    ("tiny-solar-open2", {"state": (3, 5, 4, 16, 16), "tail": (3, 5, 3, 192)}),
    ("tiny-nemotron-3-super", {"state": (5, 5, 2, 16, 64),
                               "tail": (5, 5, 3, 192)}),
    ("tiny-opt", {}),
])
def test_state_pools_have_the_parents_shapes(preset, shapes):
    """``kv_cache._state_shapes`` asks the mixer's record and reads no
    ``cfg.kda_*`` / ``cfg.mamba_*`` field itself; what it answers is what it
    answered at the parent of PR 50."""
    got = kv_cache._state_shapes(transformer_config(preset), 5, jnp.bfloat16)
    assert {k: v[0] for k, v in got.items()} == shapes
    if shapes:
        assert got["state"][1] == jnp.float32
        assert got["tail"][1] == jnp.bfloat16


def test_only_the_model_names_a_mixer_by_string():
    """What a mixer is lives in its record: outside ``models/transformer.py``
    (the table, ``LAYER_KINDS``, the mixers' bodies) and
    ``models/presets.py`` (which states configurations) no code of the
    package holds the literal of a recurrent mixer's name, to compare with
    or to look up by. A docstring may."""
    package = REPO / "deepspeed_tpu"
    owners = {package / "models" / "transformer.py",
              package / "models" / "presets.py"}
    recurrent = {m for m, record in T.MIXERS.items() if record.state}
    assert recurrent == {"kda", "mamba2", "mamba1"}
    for path in sorted(set(package.rglob("*.py")) - owners):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and node.value in recurrent:
                raise AssertionError(
                    f"{path.relative_to(REPO)}:{node.lineno}: "
                    f"{node.value!r}")
    # and inside the owner: the table, LAYER_KINDS, nothing that branches
    source = (package / "models" / "transformer.py").read_text()
    assert "if mixer ==" not in source and "elif mixer ==" not in source


@pytest.mark.parametrize("mixer,keeps,state,hands_on", [
    ("attn", "pages", False, False), ("full", "pages", False, False),
    ("swa", "ring", False, False), ("cross", None, False, False),
    ("gmu", None, False, False), ("mamba1", None, True, True),
    ("mamba2", None, True, False), ("kda", None, True, False)])
def test_a_record_says_what_its_mixer_keeps(mixer, keeps, state, hands_on):
    """What a layer keeps for a sequence is its record's word: pages of its
    own, a ring a row, a recurrent state, or nothing (it reads what another
    layer made); the three forms of the softmax mixer share one subtree name
    and one function."""
    record = T.MIXERS[mixer]
    assert (record.keeps, bool(record.state), record.hands_on) \
        == (keeps, state, hands_on)
    if mixer in ("swa", "full", "cross"):
        assert record.name == "attn"
    cfg = transformer_config("tiny-phi4flash")
    assert T.paged_layers(cfg) == (5,) and T.ring_layers(cfg) == (1, 3)
    assert T.pool_readers(cfg) == (5, 7) and T.tail_runs(cfg) == 1
    plain = transformer_config("tiny-opt")
    assert T.paged_layers(plain) == (0, 1) and T.ring_layers(plain) == ()
    assert T.pool_readers(plain) == () and T.tail_runs(plain) == 0


def test_a_layer_takes_its_operands_as_one_record():
    """``_layer_forward`` and each mixer take a ``Step``; the operand list is
    its fields and nobody's parameters."""
    assert list(inspect.signature(T._layer_forward).parameters) == [
        "cfg", "x", "layer", "step", "kind"]
    for record in T.MIXERS.values():
        assert list(inspect.signature(record.apply).parameters)[-1] == "step"
        assert len(inspect.signature(record.apply).parameters) == 4
    fields = [f.name for f in dataclasses.fields(T.Step)]
    assert "layer_index" in fields and "paged_layer" not in fields
