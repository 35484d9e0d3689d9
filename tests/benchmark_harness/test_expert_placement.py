"""`share.placement`: the experts a chip holds of each layer PLACED in balance
(`program.place_held_experts` and its policy `balanced_order`; the walk over
the layers is `references/<family>.py`'s). The policy alone, at the counts of
experts and held that the catalog's models state; then on the Solar
program's small model: the placement relabels each layer's router outputs and
touches nothing else; the held outputs then carry their part of a calibration
batch's assignments in every layer, whatever the seed, and the held experts
that a decode step touches follow the seed far less than the router's first
ones do; the cell is served `correct` with the key and without it; and
without the key nothing is computed, for any configuration file there is."""

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (device, program,  # noqa: E402
                                spec as spec_mod, traffic)

SPEC = spec_mod.Spec()
CELL = "solar-open2-250b-ep8-d4.serve-decode-r64"
CONFIG_FILES = sorted(os.listdir(SPEC.path("configs")))
PLACEMENT = spec_mod.PLACEMENT
SEEDS = [2 ** 31 + 100 + i for i in range(10)]
# stated distances: the held outputs' part of the calibration assignments
# from 1/ways (the small model: 8 of 64 outputs held, every 8th of the order
# by load, so the part is an eighth to the rounding of 8 draws), and the
# factor by which the placement narrows, over SEEDS, the variance of the held
# experts that a step of 16 rows touches (measured 8.2; 2 is stated)
PART_WITHIN = 0.01
VARIANCE_FACTOR = 2.0


def _tiny_cell(placement=PLACEMENT):
    cell = SPEC.cell(CELL)
    cell.config = bench_tiny.tiny_config(cell.config)
    cell.config["share"].pop("placement", None)
    if placement:
        cell.config["share"]["placement"] = placement
    return cell


# experts and held, as the catalog's expert models state them beside the
# small model's: Solar's 40 of 320 and shares of 12 of 384, 16, 32 and 128
# of 256 (ISSUE 38)
COUNTS = [(64, 8), (320, 40), (384, 12), (256, 16), (256, 32), (256, 128)]


@pytest.mark.parametrize("experts,held", COUNTS)
def test_balanced_order_holds_one_of_every_group_of_neighbours(experts,
                                                               held):
    """The policy alone: a permutation of the outputs whose first `held` are
    one output of every `experts / held` neighbours by load, the middle one,
    so that they carry their part of ANY load; the rest keep their order."""
    ways = experts // held
    rng = np.random.RandomState(experts + held)
    # skewed, as a random router's is: log-normal, sigma 1 (at sigma 1.5 the
    # middle of 32 neighbours misses the tail: 12 of 384 then carry 0.74 of
    # an even share, where 8 ways carry 0.97-1.01; PERF.md section 7 r)
    load = rng.poisson(np.exp(rng.normal(3.0, 1.0, experts))).astype(np.int32)
    order = np.asarray(program.balanced_order(load, held))
    assert sorted(order) == list(range(experts))
    by_load = np.argsort(-load, kind="stable")
    rank = {int(e): r for r, e in enumerate(by_load)}
    groups = [rank[int(e)] // ways for e in order[:held]]
    assert groups == list(range(held))              # one of each, in turn
    middle = {(ways - 1) // 2, ways // 2}
    assert {rank[int(e)] % ways for e in order[:held]} <= middle
    assert list(order[held:]) == sorted(order[held:])
    part = load[order[:held]].sum() / load.sum()
    # the held outputs' part lies between what the lightest and the heaviest
    # of every group would carry, and near an even share
    lightest = load[by_load].reshape(held, ways)[:, -1].sum() / load.sum()
    heaviest = load[by_load].reshape(held, ways)[:, 0].sum() / load.sum()
    assert lightest <= part <= heaviest
    assert abs(part - 1 / ways) <= 0.12 / ways, (part, 1 / ways)


def test_balanced_order_breaks_ties_by_index():
    order = np.asarray(program.balanced_order(np.full((16,), 5, np.int32), 4))
    assert list(order[:4]) == [1, 6, 9, 14]     # groups of 4: 2nd, 3rd in turn
    assert list(order[4:]) == [0, 2, 3, 4, 5, 7, 8, 10, 11, 12, 13, 15]


@pytest.fixture(scope="module")
def small():
    """The small model, its reference, and one jitted count of the experts
    every layer's router chose."""
    import jax

    cell = _tiny_cell()
    model = program.build_model(cell)
    ref, args = program.reference_module(cell), program.reference_args(cell)
    return {"cell": cell, "model": model, "init": jax.jit(model.init),
            "ways": spec_mod.expert_ways(cell.config),
            "held": model.config.experts_held,
            "place": jax.jit(lambda p, i: ref.place_held_experts(
                p, i, program.balanced_order, **args)),
            "choices": jax.jit(lambda p, i: ref.router_choices(
                p, i, **args))}


@pytest.fixture(scope="module")
def over_seeds(small):
    """For each of SEEDS: the held outputs' part of the calibration
    assignments a layer, and the held experts a step of 16 rows touches
    (summed over the layers, mean over 24 positions) as drawn and placed."""
    import jax

    vocab, held = small["model"].config.vocab_size, small["held"]

    def touched(params, ids):
        c = np.asarray(small["choices"](params, ids))       # (L, B, S, k)
        return float(np.mean([
            sum(len({int(e) for e in c[layer, :, t].ravel() if e < held})
                for layer in range(c.shape[0]))
            for t in range(c.shape[2] // 2, c.shape[2])]))

    out = {}
    for seed in SEEDS:
        params = small["init"](jax.random.PRNGKey(program.program_seed(seed)))
        ids = traffic.calibration_ids(seed, *program.CALIBRATION_BATCH,
                                      vocab)
        moved, load = small["place"](params, ids)
        load = np.asarray(load)
        steps = traffic.prompt_ids(seed, 7, 16 * 48, vocab).reshape(16, 48)
        out[seed] = {
            "part": load[:, :held].sum(1) / load.sum(1),
            "drawn": touched(params, steps),
            "placed": touched(program._with_leaves(params, moved), steps)}
    return out


def test_placement_permutes_router_outputs_and_nothing_else(small, capsys):
    import jax

    seed = SEEDS[0]
    params = small["init"](jax.random.PRNGKey(program.program_seed(seed)))
    placed = program.place_held_experts(
        small["cell"], params, seed, small["model"].config.vocab_size)
    note = json.loads(capsys.readouterr().out.splitlines()[-1])["placement"]
    assert note["experts_held"] == small["held"]
    assert note["calibration_tokens"] == int(np.prod(
        program.CALIBRATION_BATCH))
    assert jax.tree.structure(placed) == jax.tree.structure(params)
    was = dict(jax.tree_util.tree_leaves_with_path(params))
    moved = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(placed):
        name = jax.tree_util.keystr(path)
        old = was[path]
        assert (leaf.shape, leaf.dtype, leaf.sharding) == (
            old.shape, old.dtype, old.sharding), name
        if not name.endswith(("['router']", "['router_bias']")):
            assert leaf is old, name        # not a copy: the same array
            continue
        moved += 1
        if name.endswith("['router']"):
            bias = np.asarray(placed_at(placed, path[:-1])["router_bias"])
            old_bias = np.asarray(placed_at(params, path[:-1])["router_bias"])
            new, old = np.asarray(leaf), np.asarray(old)
            for layer in range(new.shape[0]):
                # each new column is one old column, each old one once, and
                # the bias went with it
                order = [int(np.flatnonzero(
                    (old[layer] == new[layer][:, [j]]).all(0))[0])
                    for j in range(new.shape[2])]
                assert sorted(order) == list(range(new.shape[2]))
                assert order != list(range(new.shape[2]))
                np.testing.assert_array_equal(bias[layer],
                                              old_bias[layer][order])
    assert moved == 4           # a router and its bias, a kind of layer


def placed_at(tree, path):
    for key in path:
        tree = tree[key.key]
    return tree


@pytest.mark.parametrize("seed", SEEDS)
def test_held_outputs_carry_their_part_in_every_layer(seed, small,
                                                      over_seeds):
    part = over_seeds[seed]["part"]
    assert part.shape == (small["model"].config.num_layers,)
    assert np.abs(part - 1 / small["ways"]).max() <= PART_WITHIN, part


def test_placement_steadies_the_held_experts_a_step_touches(over_seeds):
    drawn = np.array([r["drawn"] for r in over_seeds.values()])
    placed = np.array([r["placed"] for r in over_seeds.values()])
    assert drawn.var(ddof=1) >= VARIANCE_FACTOR * placed.var(ddof=1), (
        drawn, placed)
    # a placement that picked the popular experts, or the unpopular, would
    # move the mean: it stays within a step's own scatter
    assert abs(placed.mean() - drawn.mean()) <= drawn.std(ddof=1), (
        drawn.mean(), placed.mean())


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_without_the_key_nothing_is_placed(name):
    """Every configuration file there is, its `placement` taken out where it
    states one: the harness hands the engine's parameters on untouched (it
    does not so much as look at them)."""
    cell = copy.copy(SPEC.cell(CELL))
    cell.config = json.load(open(SPEC.path("configs", name)))
    cell.config.get("share", {}).pop("placement", None)
    assert program.place_held_experts(cell, object(), 2 ** 31 + 5,
                                      1000) is None


@pytest.mark.parametrize("form", ["a-list", "an-object", "no-share",
                                  "no-experts-divided"])
def test_expert_ways_reads_the_experts_own_ways(form):
    cfg = copy.deepcopy(SPEC.cell(CELL).config)
    if form == "a-list":
        assert spec_mod.expert_ways(cfg) == 8
        return
    if form == "an-object":
        cfg["share"].update(chips=32, divided={"n_routed_experts": 32,
                                               "vocab_size": 8})
        assert spec_mod.expert_ways(cfg) == 32
        return
    if form == "no-share":
        del cfg["share"]
    else:
        cfg["share"]["divided"] = ["vocab_size"]
    with pytest.raises(spec_mod.SpecError, match="names 0 counts of experts"):
        spec_mod.expert_ways(cfg)


@pytest.mark.parametrize("placement", [PLACEMENT, None],
                         ids=["placed", "as-drawn"])
def test_the_tiny_cell_is_served_correct(placement, tmp_path, monkeypatch,
                                         capsys):
    """The cell end to end on the CPU with the key and without it: served
    log-probabilities within 1e-4 of the reference, which is handed the
    parameters the engine served (placed, where the file says so)."""
    root = bench_tiny.make_root(str(tmp_path))
    spec = spec_mod.Spec(root)
    path = spec.path("configs", f"{spec.cell(CELL).config_name}.json")
    json.dump(_tiny_cell(placement).config, open(path, "w"))
    spec.validate()
    monkeypatch.setitem(device.TARGET, "platform", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(root, ".jax_cache"))
    result = bench_run.run_cell(spec, CELL, 2 ** 31 + 41, 3.0, False,
                                time.perf_counter())
    out = capsys.readouterr().out
    assert result["correct"] and result["failed"] == 0, out
    notes = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert next(n for n in notes if "ttft_samples" in n)[
        "reference_logprob_maxdiff"] <= 1e-4
    phases = next(n for n in notes if "setup_phases_s" in n)["setup_phases_s"]
    assert ("placement" in phases) == bool(placement)
    assert any("placement" in n for n in notes) == bool(placement)
