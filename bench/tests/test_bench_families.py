"""Model families: found by name, their weights in the program's tree,
and a family other than ``dense_gqa`` (``tests/families/moe_gqa.py``,
a routed mixture of experts with qk-norm) through the whole run on the
CPU: a sound run is correct, and one whose routing is broken is not."""
import json
import os
import sys
import time

import numpy as np
import pytest

from bench import cells, families, harness
from bench.families import dense_gqa

SEED = 2**33 + 9
TESTS = os.path.dirname(os.path.abspath(__file__))
TEST_FAMILIES = os.path.join(TESTS, "families")


def program_trees(config: dict):
    """The program's parameter specs (``SpecBuilder(...).params()``)
    and shapes for a configuration, and the family's ``init`` shapes at
    its dims; nothing is allocated."""
    import jax

    from repro.launch.mesh import make_submeshes
    from repro.launch.sharding import SpecBuilder
    from repro.models import get_config
    from repro.models.model import init_params
    cfg = get_config(config["arch"], reps=0,
                     **cells.program_overrides(config))
    mesh = make_submeshes(1, devices=jax.devices()[:1])[0]
    specs = SpecBuilder(cfg, mesh, mode="serve").params()
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    m = cells.model_dims(config)
    dtype = {"float32": jax.numpy.float32,
             "bfloat16": jax.numpy.bfloat16}[cfg.param_dtype]
    got = jax.eval_shape(
        lambda k: families.load(m["family"]).init(m, k, dtype),
        jax.random.PRNGKey(0))
    return specs, want, got


def assert_tree_is_the_programs(config: dict):
    import jax
    specs, want, got = program_trees(config)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa
    assert jax.tree.structure(got) == \
        jax.tree.structure(specs, is_leaf=is_spec)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype), path


def _moe_config():
    with open(os.path.join(TESTS, "configs", "tiny-moe.json")) as f:
        return json.load(f)


def _moe_cell():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    mix = {"loop": "closed", "clients": 2, "prompt_bytes": 16,
           "max_tokens": 16}
    return cells.Cell(name="tiny-moe", chips=1, config=_moe_config(),
                      traffic=mix, end_to_end=b["end_to_end"],
                      per_layer=b["per_layer"])


@pytest.fixture
def moe_family(monkeypatch):
    """Model families found in this directory's ``families/``."""
    monkeypatch.setattr(families, "FAMILIES_DIR", TEST_FAMILIES)


def test_unknown_family_names_the_known_ones():
    with pytest.raises(cells.CellError, match="dense_gqa"):
        families.load("no_such_family")
    with pytest.raises(cells.CellError, match="moe_gqa"):
        families.load("dense_gqa", TEST_FAMILIES)
    cfg = dict(_moe_config(), reference="no_such_family")
    with pytest.raises(cells.CellError, match="no model family"):
        cells.model_dims(cfg)


def test_moe_family_tree_is_the_programs(moe_family):
    assert_tree_is_the_programs(_moe_config())


def test_moe_layer_flops_by_hand(moe_family):
    m = cells.model_dims(_moe_config())
    fam = families.load("moe_gqa")
    # q 128x128, k and v 128x64 each, o 128x128, router 128x4, two of
    # the four experts at 3 x 128x64: 2 FLOPs a MAC
    macs = 128 * 128 + 2 * 128 * 64 + 128 * 128 + 128 * 4 + 2 * 3 * 128 * 64
    attn = 4 * 3 * 5 * 4 * 32
    assert fam.layer_flops(m, 3, 5) == 2 * 3 * macs + attn


def test_attend_masks_by_position():
    """A mask per query: each query over the keys up to its own
    position reads as that query alone over those keys; a per-query
    mask whose rows are all one key mask reads as that key mask."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 5, 2, 2, 8))
    k = jax.random.normal(ks[1], (2, 5, 2, 8))
    v = jax.random.normal(ks[2], (2, 5, 2, 8))
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((5, 5), bool)), (2, 5, 5))
    got = dense_gqa.attend(q, k, v, causal, 8, chunk=2)
    for i in range(5):
        alone = dense_gqa.attend(q[:, i:i + 1], k[:, :i + 1], v[:, :i + 1],
                                 jnp.ones((2, i + 1), bool), 8)
        np.testing.assert_allclose(got[:, i:i + 1], alone, rtol=1e-5,
                                   atol=1e-6)
    valid = jnp.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], bool)
    rows = jnp.broadcast_to(valid[:, None], (2, 5, 5))
    assert (dense_gqa.attend(q, k, v, rows, 8, chunk=2)
            == dense_gqa.attend(q, k, v, valid, 8, chunk=2)).all()


def _run(break_program=None):
    import jax
    return harness.run(_moe_cell(), SEED, 2.0, False, time.perf_counter(),
                       jax.devices(), log=sys.stderr,
                       break_program=break_program, compile_cache=False)


def test_moe_family_sound_run_is_correct(moe_family):
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) >= {"mean_gap", "not_top_pct"}


def _route_top1(cfg, p, x2d):
    """The program's router with each token sent to its top expert
    alone, in place of its ``moe_top_k``."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(x2d.astype(jnp.float32) @ p["router"], axis=-1)
    w, ids = jax.lax.top_k(probs, 1)
    return probs, w / w, ids


def test_moe_routing_fault_is_not_correct(moe_family, monkeypatch):
    """Top-1 in place of top-k, planted in the program before its
    block programs are built, so that the window compiles nothing and
    only the comparison with the reference can catch it."""
    from repro.models import moe
    monkeypatch.setattr(moe, "_route", _route_top1)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert any(res["checks"][k]["value"] > res["checks"][k]["limit"]
               for k in ("mean_gap", "not_top_pct"))
