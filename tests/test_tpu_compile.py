"""Compile the main path's programs for a TPU v5e that is described, not
attached, with x64 on as the solver runs.

Each Pallas kernel is compiled in f32 (Mosaic has no 64-bit types) at two
cell shapes, and must come out as a kernel (``tpu_custom_call``), not as
interpreted XLA.  The f64 certified round is compiled through XLA, which
emulates f64 on the TPU.  The topology is described inside a fixture,
never at import, so that only the pytest worker running this file loads
the TPU compiler.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SGLSession, SolverConfig, make_problem
from repro.core.sgl import SGLProblem
from repro.core.solver import _inner_rounds, _screen_round, resolve_backend
from repro.kernels import _util as kernel_util
from repro.kernels.cases import kernel_cases
from repro.rules import GapSafeRule

# (n, G, ng): the paper's synthetic problem and the NCEP/NCAR climate design
# at its published width (144 x 73 grid points less the target point, in
# groups of 7 variables: p = 73,577).
SHAPES = {"paper-synth": (100, 1000, 10), "climate-full": (814, 10511, 7)}
KERNELS = sorted(kernel_cases(8, 8, 8, np.float32))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Make the dispatch code take its TPU branch (compiled kernels), with
    the jit caches cleared on both sides so no trace crosses the switch."""
    jax.clear_caches()
    monkeypatch.setattr(kernel_util, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("cell", sorted(SHAPES))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_f32_under_x64(one_chip, as_tpu, name, cell):
    assert jax.config.read("jax_enable_x64")
    n, G, ng = SHAPES[cell]
    case = kernel_cases(n, G, ng, np.float32)[name]
    args = _on(one_chip, jax.eval_shape(case.make_args,
                                        jax.random.PRNGKey(0)))
    text = jax.jit(case.fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cell", sorted(SHAPES))
def test_screen_round_f64_xla_compiles(one_chip, cell):
    n, G, ng = SHAPES[cell]
    f64 = jnp.float64
    S = jax.ShapeDtypeStruct
    problem = SGLProblem(
        X=S((n, G, ng), f64), y=S((n,), f64), w=S((G,), f64),
        tau=S((), f64), feat_mask=S((G, ng), jnp.bool_), Lg=S((G,), f64),
        Xnorm_col=S((G, ng), f64), Xnorm_grp=S((G,), f64))
    args = _on(one_chip, (problem, S((G, ng), f64), S((), f64),
                          S((), f64)))
    compiled = _screen_round.lower(*args, rule=GapSafeRule(),
                                   backend="xla").compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_epoch_block_f64_xla_compiles(one_chip):
    """The f64 epoch block (live-bounded group loop inside the blocked
    while loop) at the largest bucket the paper-synth path fills."""
    n, G, ng = SHAPES["paper-synth"]
    Gb = 256
    f64 = jnp.float64
    S = jax.ShapeDtypeStruct
    args = _on(one_chip, (
        S((Gb, n, ng), f64), S((Gb,), f64), S((Gb,), f64), S((n,), f64),
        S((G, ng), f64), S((G, ng), jnp.bool_), S((Gb,), jnp.int64),
        S((Gb,), f64), S((), f64), S((), f64), S((), f64)))
    compiled = _inner_rounds.lower(*args, block_epochs=1, max_blocks=100,
                                   backend="xla").compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_auto_backend_follows_platform_and_dtype(monkeypatch):
    monkeypatch.setattr(kernel_util, "on_tpu", lambda: True)
    assert resolve_backend("auto", np.float64) == "xla"
    assert resolve_backend("auto", np.float32) == "pallas"
    X = np.eye(4)
    problem = make_problem(X, np.ones(4), [2, 2], tau=0.5)
    with pytest.raises(ValueError, match="64-bit"):
        SGLSession(problem, SolverConfig(solver_backend="pallas"))
    with pytest.raises(ValueError, match="64-bit"):
        SGLSession(problem, SolverConfig(screen_backend="pallas"))
    session = SGLSession(problem, SolverConfig())
    assert (session.backend, session.solver_backend) == ("xla", "xla")
