"""The float32 reference against the program's own forward pass, on the
CPU at a tiny size, with the weights the benchmark draws."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401

import reference
import server as S
from repro import compat
from repro.models import model as M

CFG = json.loads((_paths.BENCH / "tests" / "data" / "tiny.json").read_text())


@pytest.fixture(scope="module")
def weights():
    mesh = S.make_mesh(CFG)
    return mesh, S.draw_weights(CFG, 2**33 + 5, mesh)


def program_logits(mesh, params, tokens):
    ctx = S.context(CFG)

    def fwd(p, t):
        return M.forward(p, t, ctx)[0]

    fn = jax.jit(compat.shard_map(
        fwd, mesh=mesh, in_specs=(M.param_specs(ctx), jax.sharding.PartitionSpec()),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False))
    return np.asarray(fn(params, jnp.asarray([tokens], jnp.int32)))[0]


def test_reference_matches_program_logits(weights):
    mesh, params = weights
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, CFG["vocab_size"], 70).tolist()
    at = np.arange(len(tokens))
    ref = reference.logits(params, CFG, tokens, at)
    prog = program_logits(mesh, params, tokens)
    assert ref.shape == prog.shape == (70, CFG["vocab_size"])
    # the program computes in bf16: its logits (std ~1) stay within a few
    # bf16 roundings of the float32 reference
    assert float(np.abs(ref - prog).max()) < 0.1 * float(ref.std())
    assert (ref.argmax(-1) == prog.argmax(-1)).mean() > 0.9


def test_reference_is_causal(weights):
    _, params = weights
    rng = np.random.default_rng(1)
    a = rng.integers(0, CFG["vocab_size"], 40).tolist()
    b = a[:25] + rng.integers(0, CFG["vocab_size"], 15).tolist()
    at = np.arange(25)
    np.testing.assert_array_equal(reference.logits(params, CFG, a, at),
                                  reference.logits(params, CFG, b, at))


def test_control_rounds_the_weights(weights):
    _, params = weights
    tokens = list(range(3, 40))
    at = np.arange(len(tokens))
    ref = reference.logits(params, CFG, tokens, at)
    for quant in ("int8", "fp8"):
        ctl = reference.logits(params, CFG, tokens, at, quant)
        assert 0 < float(np.abs(ctl - ref).max()) < float(ref.std())
