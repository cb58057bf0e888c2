"""chip_smoke.py's routines at ``tiny`` size on CPU, with the Pallas
kernels in interpret mode, and its refusal to run without a TPU."""
import importlib.util
import os

import pytest

from repro.launch.mesh import make_submeshes
from repro.models import get_config
from repro.serving import DecodeExecutor

ROOT = os.path.join(os.path.dirname(__file__), "..")
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

P, G = 16, 16


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny")
    return cfg, DecodeExecutor.random_init(cfg, make_submeshes(1)[0], seed=3)


@pytest.fixture(scope="module")
def engines(tiny):
    cfg, ex = tiny
    engs = [smoke.make_engine(cfg, ex, G, max_slots=4) for _ in range(2)]
    reps = smoke.prewarm(engs, P, G)
    assert all(r["batch_sizes"] == [1, 2, 4] for r in reps)
    return engs


@pytest.fixture(scope="module")
def fleet(tiny):
    """Engines as the --chips 4 phase builds them: every gang padded to
    ``FLEET_GANG`` rows."""
    cfg, ex = tiny
    engs = [smoke.make_engine(cfg, ex, G, smoke.FLEET_GANG,
                              batch_multiple=smoke.FLEET_GANG)
            for _ in range(2)]
    reps = smoke.prewarm(engs, P, G)
    assert all(r["batch_sizes"] == [1, smoke.FLEET_GANG] for r in reps)
    return engs


def test_serve_and_check_one_engine(engines):
    prompts = smoke.make_prompts(6, P, seed=1)
    assert len({len(p) for p in prompts}) == 1
    out = smoke.serve_and_check(engines[:1], prompts, G)
    assert out["served"] == [6]
    assert sum(out["finish"].values()) == 6
    assert out["host_syncs_per_block"] == [1.0]
    assert out["post_warm_compiles"] == 0
    assert all(len(t) <= G for t in out["tokens"].values())


def test_two_engines_match_one(fleet):
    """The --chips 4 comparison at CPU scale: with every gang the same
    size, a row's tokens do not depend on its gang mates, so a router
    over two engines returns exactly the tokens one engine does, and
    both engines serve."""
    prompts = smoke.make_prompts(6, P, seed=2)
    one = smoke.serve_and_check(fleet[:1], prompts, G)
    two = smoke.serve_and_check(fleet, prompts, G)
    assert sum(two["served"]) == 6 and min(two["served"]) >= 1
    assert one["tokens"] == two["tokens"]


def test_failed_request_fails_the_check(engines):
    """A request that ends any other way than stop/length (here: a
    max_tokens the server refuses) fails the smoke run."""
    with pytest.raises(smoke.SmokeFailure, match="HTTP 400"):
        smoke.serve_and_check(engines[:1], ["x" * P], 0)


def test_kernel_parity_tiny(tiny):
    cfg, ex = tiny
    err = smoke.kernel_parity(cfg, ex.params, prompt_len=P, gen_len=G)
    assert err["prefill"] <= smoke.LOGIT_RTOL
    assert err["step"] <= smoke.LOGIT_RTOL
    assert err["step_bf16_attention"] > smoke.LOGIT_RTOL


def test_gang_witness_tiny(tiny):
    """No row leaks into another: row 0 is bit-identical across gang
    mates. The gang's size moves a row's logits at the rounding level
    only (XLA:CPU too), within the bound the chip run checks."""
    cfg, ex = tiny
    wit = smoke.gang_witness(cfg, ex.params, prompt_len=P, gen_len=G)
    for w in wit.values():
        assert w["cross_row"] == 0.0
        assert w["gang_size"] <= smoke.LOGIT_RTOL


def test_interpreted_kernels_are_not_custom_calls(tiny, engines):
    """On CPU the kernels run interpreted: they lower to plain HLO, so
    the compiled block program holds no tpu_custom_call — the check
    the chip run relies on tells the two apart."""
    from repro.kernels.ops import compiled_kernels
    prog = smoke.compile_block(engines[0], P, G, batch=2)
    assert not set(smoke.KERNELS) & compiled_kernels(prog.as_text())


def test_main_refuses_without_tpu(capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
