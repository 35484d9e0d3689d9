"""The seam of the model layer: a mixer is ONE record in
``models/transformer.MIXERS`` and a step's operands are ONE record, ``Step``.

What holds the seam in place: seeded parameters are the bits they were before
the mixers moved behind the table (digests recorded from the parent of PR 50,
leaf by leaf); a FOURTH mixer that only this file knows goes through init,
axes, the cache's shapes and ``forward`` with and without pages; the state
pools of the two recurrent families have the shapes they had; and no module
but the model's own names a mixer by its string.

The same for an FFN since PR 65: ONE record in ``FFNS`` and ONE function,
``ffn_of``, that says which a kind's layer has. Digests of the dense SwiGLU,
the sublayers' dense FFN, zero experts and the PR-MoE residual from the
parent of PR 65; a FOURTH FFN that only this file knows, in every second
layer; and no module but the model's own names an expert leaf.
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
from deepspeed_tpu.models.core import EMBED, LAYERS, MLP
from deepspeed_tpu.models.presets import transformer_config

REPO = pathlib.Path(__file__).resolve().parents[2]

# sha256 (first ten hex digits) of every leaf of
# ``init_params(PRNGKey(0), transformer_config(preset))`` on the CPU: the
# first four at c6e4150, the parent of PR 50; the others (a key
# ``preset+flag`` is the preset with that flag set) at 32d3238, the parent of
# PR 65, before the FFNs moved behind ``FFNS``
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
    "tiny-llama": """
        embed/tokens=6865c2299c final_norm/scale=2f20cd03c9
        layers/attn/wk=4479327429 layers/attn/wo=881bdeb2da
        layers/attn/wq=6f783c6a6d layers/attn/wv=2e1e8a9c63
        layers/ln1/scale=02722f124d layers/ln2/scale=02722f124d
        layers/mlp/w_down=90cb5f6a23 layers/mlp/w_gate=b49b20f6d0
        layers/mlp/w_up=2485f9c27d lm_head=1d87c77b8f""",
    "tiny-phi4flash": """
        embed/tokens=6865c2299c final_norm/bias=5341e6b264
        final_norm/scale=2f20cd03c9 layers/cross/attn/bo=350070fd25
        layers/cross/attn/bq=b0189c0f1b layers/cross/attn/lam_init=aa58269973
        layers/cross/attn/lam_k1=b019daabc0
        layers/cross/attn/lam_k2=9afe701f5a
        layers/cross/attn/lam_q1=5b55b82927
        layers/cross/attn/lam_q2=2a3e6d0c0e layers/cross/attn/subln=9628e545ed
        layers/cross/attn/wo=aa9b5c272c layers/cross/attn/wq=e61db7b240
        layers/cross/ln1/bias=5341e6b264 layers/cross/ln1/scale=2f20cd03c9
        layers/cross/ln2/bias=5341e6b264 layers/cross/ln2/scale=2f20cd03c9
        layers/cross/mlp/w_down=48ce97928d layers/cross/mlp/w_gate=27fa6e527e
        layers/cross/mlp/w_up=4493520d4e layers/full/attn/bk=07c5eda096
        layers/full/attn/bo=b459c3c99c layers/full/attn/bq=34f6ed6b04
        layers/full/attn/bv=44568004f2 layers/full/attn/lam_init=b8d416d50a
        layers/full/attn/lam_k1=870fdf4847 layers/full/attn/lam_k2=d525effc8e
        layers/full/attn/lam_q1=b8f082cd3f layers/full/attn/lam_q2=a0185be967
        layers/full/attn/subln=9628e545ed layers/full/attn/wk=136ece3564
        layers/full/attn/wo=48110d70aa layers/full/attn/wq=3943bd36bf
        layers/full/attn/wv=368ee1efc8 layers/full/ln1/bias=5341e6b264
        layers/full/ln1/scale=2f20cd03c9 layers/full/ln2/bias=5341e6b264
        layers/full/ln2/scale=2f20cd03c9 layers/full/mlp/w_down=e320a65c51
        layers/full/mlp/w_gate=37f287be12 layers/full/mlp/w_up=d8e98f1e9b
        layers/gmu/gmu/w_in=97950dd0c3 layers/gmu/gmu/w_out=662fac0a51
        layers/gmu/ln1/bias=5341e6b264 layers/gmu/ln1/scale=2f20cd03c9
        layers/gmu/ln2/bias=5341e6b264 layers/gmu/ln2/scale=2f20cd03c9
        layers/gmu/mlp/w_down=ec10b90a95 layers/gmu/mlp/w_gate=e6223e3d6c
        layers/gmu/mlp/w_up=2a5ab9b3c7 layers/mamba1/ln1/bias=ef115a0e0c
        layers/mamba1/ln1/scale=5ce183e97a layers/mamba1/ln2/bias=ef115a0e0c
        layers/mamba1/ln2/scale=5ce183e97a
        layers/mamba1/mamba1/A_log=f81f956b93
        layers/mamba1/mamba1/D=702cc37cfd
        layers/mamba1/mamba1/conv_b=3a67a4ee28
        layers/mamba1/mamba1/conv_w=8246f8f5a1
        layers/mamba1/mamba1/dt_bias=4b03128f8a
        layers/mamba1/mamba1/w_dt=1310a13194
        layers/mamba1/mamba1/w_in=682920e888
        layers/mamba1/mamba1/w_out=6af74b8054
        layers/mamba1/mamba1/w_x=44b52ca7f0
        layers/mamba1/mlp/w_down=55a6350b3e
        layers/mamba1/mlp/w_gate=2163605c29 layers/mamba1/mlp/w_up=5e0d5ce259
        layers/swa/attn/bk=9efbc90ca6 layers/swa/attn/bo=8770a13397
        layers/swa/attn/bq=f269259a66 layers/swa/attn/bv=143854c0af
        layers/swa/attn/lam_init=170dc9ae5c layers/swa/attn/lam_k1=f751cbb02d
        layers/swa/attn/lam_k2=af66f2f7d9 layers/swa/attn/lam_q1=4b519d803f
        layers/swa/attn/lam_q2=c4c54da73e layers/swa/attn/subln=b638277a86
        layers/swa/attn/wk=b93925633b layers/swa/attn/wo=0144562891
        layers/swa/attn/wq=faf492c1bb layers/swa/attn/wv=af9e1c4b7a
        layers/swa/ln1/bias=076a27c79e layers/swa/ln1/scale=02722f124d
        layers/swa/ln2/bias=076a27c79e layers/swa/ln2/scale=02722f124d
        layers/swa/mlp/w_down=77c9f60f56 layers/swa/mlp/w_gate=e41abf2ac7
        layers/swa/mlp/w_up=86df150b30""",
    "tiny-ouro": """
        embed/tokens=6865c2299c exit_gate/b=f04da81a20 exit_gate/w=c11455c38e
        final_norm/scale=2f20cd03c9 layers/attn/wk=12281c7974
        layers/attn/wo=50b5ec92b4 layers/attn/wq=630b34146f
        layers/attn/wv=2be654f8e7 layers/ln1/scale=893a106828
        layers/ln1_post/scale=893a106828 layers/ln2/scale=893a106828
        layers/ln2_post/scale=893a106828 layers/mlp/w_down=b851ad6f75
        layers/mlp/w_gate=12bfdbf19d layers/mlp/w_up=e3b19870ca
        lm_head=1d87c77b8f""",
    "tiny-longcat-flash": """
        embed/tokens=6865c2299c final_norm/scale=2f20cd03c9
        layers/dense/w_down=02f5e08d4f layers/dense/w_gate=8d94006bfc
        layers/dense/w_up=76d96c7d57 layers/ln1/scale=893a106828
        layers/ln2/scale=893a106828 layers/mla/kv_norm=23a90de394
        layers/mla/q_norm=02722f124d layers/mla/wk_b=6041523cc1
        layers/mla/wkv_a=71ee45d9b9 layers/mla/wo=980422941f
        layers/mla/wq_a=9a865c0904 layers/mla/wq_b=22023940c3
        layers/mla/wv_b=dac42ceef6 layers/mlp/w_down=659fc22140
        layers/mlp/w_gate=f553d62dd4 layers/mlp/w_up=00a682abf9
        layers/router=b7b1df4893 layers/router_bias=f77a741377
        lm_head=1d87c77b8f""",
    "moe-tiny+moe_use_residual": """
        embed/tokens=6865c2299c final_norm/bias=5341e6b264
        final_norm/scale=2f20cd03c9 layers/attn/bk=076a27c79e
        layers/attn/bo=076a27c79e layers/attn/bq=076a27c79e
        layers/attn/bv=076a27c79e layers/attn/wk=6340c995c5
        layers/attn/wo=881bdeb2da layers/attn/wq=6f783c6a6d
        layers/attn/wv=911a95ba58 layers/ln1/bias=076a27c79e
        layers/ln1/scale=02722f124d layers/ln2/bias=076a27c79e
        layers/ln2/scale=02722f124d layers/mlp/w_down=862373f451
        layers/mlp/w_up=479370d1b3 layers/res_coef/b=374708fff7
        layers/res_coef/w=25e67b1d30 layers/res_mlp/b_down=076a27c79e
        layers/res_mlp/b_up=e5a00aa999 layers/res_mlp/w_down=b769db4c25
        layers/res_mlp/w_up=497da58b47 layers/router=6a593738bf pos=53e217c7b8""",
}


@pytest.mark.parametrize("preset", sorted(PARENT_DIGESTS))
def test_seeded_parameters_are_the_parents_bits(preset):
    """The harness's reference compares on seeded weights: a mixer's
    ``fold_in`` tags, shapes and dtypes are its own for good."""
    preset, *flags = preset.split("+")
    params = T.init_params(jax.random.PRNGKey(0), transformer_config(
        preset, **dict.fromkeys(flags, True)))
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()[:10]
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    want = dict(item.split("=") for item in PARENT_DIGESTS[
        "+".join([preset, *flags])].split())
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


# ---------------------------------------------------------------------------
# a fourth FFN, registered here alone: a product of two projections
# ---------------------------------------------------------------------------


def _bilinear_ffn(cfg, h, layer, step, kind):
    w = layer["bilinear"]
    pair = jnp.einsum("bsh,hf->bsf", h, w["a"]) \
        * jnp.einsum("bsh,hf->bsf", h, w["b"])
    return jnp.einsum("bsf,fh->bsh", pair, w["c"]) + w["bias"], \
        jnp.float32(0.0)


BILINEAR = T.Ffn(
    init=lambda cfg, normal: {"bilinear": {
        "a": normal(91, (cfg.hidden_size, 48), 0.5),
        "b": normal(92, (cfg.hidden_size, 48), 0.5),
        "c": normal(93, (48, cfg.hidden_size)),
        "bias": jnp.zeros((cfg.hidden_size,), cfg.dtype)}},
    axes=lambda cfg: {"bilinear": {
        "a": (LAYERS, EMBED, MLP), "b": (LAYERS, EMBED, MLP),
        "c": (LAYERS, MLP, EMBED), "bias": (LAYERS, EMBED)}},
    apply=_bilinear_ffn)


@pytest.fixture
def bilinear_model(mean_model, monkeypatch):
    """``mean_model`` whose "mean" layers have the bilinear FFN and whose
    "attn" layers keep tiny-llama's SwiGLU: an FFN that differs by layer.
    Nothing of the package is edited: the FFN is one table entry, and which
    layers have it is one line of ``ffn_of``."""
    cfg, _ = mean_model
    ffn_of = T.ffn_of
    monkeypatch.setitem(T.FFNS, "bilinear", BILINEAR)
    monkeypatch.setattr(
        T, "ffn_of", lambda cfg, kind, sublayer=False: "bilinear"
        if kind == "mean" and not sublayer else ffn_of(cfg, kind, sublayer))
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_a_fourth_ffn_has_params_and_axes(bilinear_model, mean_model):
    cfg, params = bilinear_model
    H = cfg.hidden_size
    assert sorted(params["layers"]["mean"]) == ["bilinear", "ln1", "ln2",
                                                "mean"]
    assert sorted(params["layers"]["attn"]) == ["attn", "ln1", "ln2", "mlp"]
    assert params["layers"]["mean"]["bilinear"]["a"].shape == (2, H, 48)
    # the layers of the other kind, and this kind's mixer, drew what they drew
    for path, leaf in jax.tree_util.tree_flatten_with_path(mean_model[1])[0]:
        if "mlp" not in [k.key for k in path]:
            mine = params
            for k in path:
                mine = mine[k.key]
            assert np.array_equal(np.asarray(mine), np.asarray(leaf)), path
    axes = T.param_axes(cfg)
    assert axes["layers"]["mean"]["bilinear"]["c"] == (LAYERS, MLP, EMBED)
    assert "mlp" in axes["layers"]["attn"] \
        and "mlp" not in axes["layers"]["mean"]
    is_axes = lambda x: isinstance(x, tuple)       # noqa: E731
    assert (jax.tree.structure(axes, is_leaf=is_axes)
            == jax.tree.structure(params))
    for a, leaf in zip(jax.tree.leaves(axes, is_leaf=is_axes),
                       jax.tree.leaves(params)):
        assert len(a) == leaf.ndim
    assert sorted(T.ffn_layers(cfg)) == [0, 1, 2, 3]
    assert T.moe_count_width(cfg) == 3


def test_a_fourth_ffn_runs_forward_with_and_without_pages(bilinear_model):
    """As the fourth mixer's: the whole sequence with no cache against a
    ragged chunk of 5 (padded to 8) and then a token a step through the
    pages and the state pools; and training differentiates through it."""
    cfg, params = bilinear_model
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 9), 0,
                             cfg.vocab_size)
    want, _, aux = T.forward(params, ids, cfg)
    assert np.isfinite(np.asarray(want)).all() and float(aux) == 0.0
    # the bilinear FFN is in the program: without its weights the logits move
    zeroed = jax.tree.map(lambda a: a, params)
    zeroed["layers"]["mean"]["bilinear"]["c"] *= 0
    assert np.abs(np.asarray(T.forward(zeroed, ids, cfg)[0] - want)).max() \
        > 1e-3
    grads = jax.grad(T.build_model(cfg).loss_fn)(params, {"input_ids": ids})
    assert float(jnp.abs(grads["layers"]["mean"]["bilinear"]["a"]).max()) > 0

    cache = kv_cache.init_paged_cache(cfg, 9, 4, jnp.float32, state_slots=3)
    table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    slots = jnp.asarray([1], jnp.int32)

    def run(cache, tokens, pos, mask, **how):
        logits, cache, _ = T.forward(
            params, tokens, cfg, cache=cache, positions=pos,
            block_table=table, paged_write_mask=mask, state_slots=slots,
            **how)
        return np.asarray(logits), cache

    chunk = jnp.zeros((1, 8), jnp.int32).at[0, :5].set(ids[0, :5])
    got, cache = run(cache, chunk, jnp.arange(8)[None],
                     jnp.arange(8)[None] < 5,
                     paged_run=(jnp.int32(0), jnp.int32(5)))
    out = [got[0, :5]]
    for p in range(5, 9):
        got, cache = run(cache, ids[:, p:p + 1], jnp.asarray([[p]]),
                         jnp.ones((1, 1), bool))
        out.append(got[0])
    np.testing.assert_allclose(np.concatenate(out), np.asarray(want)[0],
                               atol=2e-5)
    # it routes nothing: routing counts are refused, as for any dense model
    with pytest.raises(ValueError, match="moe_counts"):
        run(cache, ids[:, :1], jnp.asarray([[0]]), jnp.ones((1, 1), bool),
            moe_counts=True)


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
    assert recurrent == {"kda", "mamba2", "mamba1", "shortconv"}
    for path in sorted(set(package.rglob("*.py")) - owners):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and node.value in recurrent:
                raise AssertionError(
                    f"{path.relative_to(REPO)}:{node.lineno}: "
                    f"{node.value!r}")
    # and inside the owner: the table, LAYER_KINDS, nothing that branches
    source = (package / "models" / "transformer.py").read_text()
    assert "if mixer ==" not in source and "elif mixer ==" not in source


EXPERT_LEAVES = {"router", "router_bias", "res_mlp", "res_coef"}


def test_only_the_model_names_an_expert_leaf():
    """What an FFN is lives in its record: outside ``models/transformer.py``
    and ``models/presets.py`` no code of the package holds the literal of a
    leaf that only the experts' FFN has. A docstring may."""
    package = REPO / "deepspeed_tpu"
    owners = {package / "models" / "transformer.py",
              package / "models" / "presets.py"}
    for path in sorted(set(package.rglob("*.py")) - owners):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and node.value in EXPERT_LEAVES:
                raise AssertionError(
                    f"{path.relative_to(REPO)}:{node.lineno}: "
                    f"{node.value!r}")


@pytest.mark.parametrize("function", [
    "init_layer_params", "param_axes", "_layer_forward",
    "_sublayers_forward", "forward", "moe_count_width",
    "quantize_model_weights"])
def test_a_function_of_the_stack_asks_which_ffn(function):
    """The functions that walk a layer ask ``ffn_of`` and the record: none
    reads ``cfg.moe_num_experts``, tests the activation for SwiGLU or holds
    an expert leaf's name (its docstring may)."""
    tree = ast.parse(inspect.getsource(getattr(T, function)))
    doc = ast.get_docstring(tree.body[0])
    for node in ast.walk(tree):
        assert not (isinstance(node, ast.Attribute)
                    and node.attr == "moe_num_experts"), node.lineno
        if isinstance(node, ast.Constant) and node.value != doc:
            assert node.value not in EXPERT_LEAVES | {"swiglu"}, node.lineno


def test_one_function_says_which_ffn_a_layer_has():
    """Of the model's functions ``ffn_of`` alone compares
    ``cfg.moe_num_experts`` to choose an FFN; the loss adds the experts'
    auxiliary term under the same test, and nothing else."""
    tree = ast.parse(inspect.getsource(T))
    assert {fn.name for fn in ast.walk(tree)
            if isinstance(fn, ast.FunctionDef) and any(
                isinstance(n, ast.Compare)
                and isinstance(n.left, ast.Attribute)
                and n.left.attr == "moe_num_experts" for n in ast.walk(fn))
            } == {"ffn_of", "build_model", "make_loss", "loss_fn"}


def test_a_record_says_what_its_ffn_counts_and_keeps_whole():
    """The experts' routing counts and the bank that stays out of the layer
    scan are its record's word; ``ffn_of`` answers from the configuration as
    the three chains did."""
    assert sorted(T.FFNS) == ["biased", "dense", "experts", "swiglu"]
    assert {name: record.whole for name, record in T.FFNS.items()} == {
        "biased": None, "dense": None, "experts": "mlp", "swiglu": None}
    longcat = transformer_config("tiny-longcat-flash")
    assert T.FFNS["experts"].count_width(longcat) == 4 \
        == T.moe_count_width(longcat)
    assert T.moe_count_width(transformer_config("tiny-olmoe")) == 3
    assert T.FFNS["swiglu"].count_width(longcat) == 0
    assert (T.ffn_of(longcat, "shortcut"),
            T.ffn_of(longcat, "shortcut", sublayer=True)) == ("experts",
                                                              "dense")
    nemotron = transformer_config("tiny-nemotron-3-super")
    assert [T.ffn_of(nemotron, k) for k in ("mamba2_mixer", "attn_mixer",
                                            "ffn")] == [None, None, "experts"]
    for preset, ffn in [("tiny-opt", "biased"), ("tiny-llama", "swiglu"),
                        ("tiny-phi4flash", "swiglu"), ("moe-tiny", "experts")]:
        cfg = transformer_config(preset)
        assert {T.ffn_of(cfg, k) for k in T.layer_kinds(cfg)} == {ffn}
        assert T.ffn_of(cfg, "attn", sublayer=True) is None


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
