"""Shared backend predicates + launch-spec metadata for the Pallas kernels.

Leaf module (imports nothing from this package) so both the kernel entry
points and their dispatch wrappers in ops.py — and the solver — can use one
spelling of the "are we on TPU" test.  When Pallas gains another compiled
backend, this is the only place to update.

:class:`LaunchSpec` / :class:`ArraySpec` are the *auditable* description of
a ``pallas_call`` launch: every kernel module builds its grid and
``BlockSpec``s from a ``*_launch_spec()`` function returning one of these,
and the SAME object feeds both the actual launch (via :func:`block_specs` /
:func:`out_shapes`) and the static analyzer
(:mod:`repro.analysis.pallas_audit`), so the audited geometry can never
drift from the executed one.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret-mode default: compile on TPU, interpret elsewhere."""
    return not on_tpu()


class ArraySpec(NamedTuple):
    """One pallas_call operand: full shape, block shape, index map, dtype.

    ``index_map`` takes the grid coordinates (python ints work — Pallas
    index maps must be pure shape arithmetic) and returns the *block*
    indices, exactly as passed to ``pl.BlockSpec``.
    """

    shape: Tuple[int, ...]
    block: Tuple[int, ...]
    index_map: Callable[..., Tuple[int, ...]]
    dtype: Any = "float64"

    @property
    def block_bytes(self) -> int:
        return int(np.prod(self.block)) * np.dtype(self.dtype).itemsize

    @property
    def array_bytes(self) -> int:
        """Full (unblocked) array footprint in bytes."""
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize

    @property
    def nblocks(self) -> Tuple[int, ...]:
        return tuple(-(-s // b) for s, b in zip(self.shape, self.block))


class LaunchSpec(NamedTuple):
    """Auditable description of one ``pallas_call`` launch.

    ``carried``: per-output tuple of grid axes the output's index map is
    declared invariant to — the VMEM-resident accumulation/carry pattern
    (e.g. the corr tile accumulating over the K axis, the BCD state carried
    across epoch/group-tile steps).  The auditor *verifies* the invariance
    and exempts exactly these axes from the exactly-once coverage check;
    an undeclared invariant axis (or a declared one that is not invariant)
    is a finding.
    """

    name: str
    grid: Tuple[int, ...]
    inputs: Tuple[ArraySpec, ...]
    outputs: Tuple[ArraySpec, ...]
    carried: Tuple[Tuple[int, ...], ...] = ()
    note: str = ""

    @property
    def vmem_bytes(self) -> int:
        """VMEM-resident footprint of one grid step (all operand blocks)."""
        return sum(a.block_bytes for a in self.inputs + self.outputs)

    @property
    def io_bytes(self) -> int:
        """Unique-bytes HBM traffic model: every operand read or written
        once at full size.  A deliberate lower bound — carried outputs stay
        VMEM-resident and streamed inputs may be re-read per epoch axis —
        used by the obs timing harness as the ``bytes`` term of
        :func:`repro.launch.roofline.achieved_vs_peak` when a kernel has no
        hand-written traffic formula."""
        return sum(a.array_bytes for a in self.inputs + self.outputs)


def _int32_index_map(index_map: Callable[..., Tuple[int, ...]]):
    """Cast every block index to int32.  With ``jax_enable_x64`` on (the
    certificate posture, :mod:`repro.core.precision`) a literal ``0`` in an
    index map traces as int64, which Mosaic refuses to lower."""
    def mapped(*idx):
        return tuple(jnp.asarray(v, jnp.int32) for v in index_map(*idx))
    return mapped


def fori_loop_i32(n: int, body, init):
    """``lax.fori_loop(0, n, body, init)`` with an int32 loop index.

    With static bounds ``fori_loop`` lowers to a scan whose counter starts
    from a python int, i.e. int64 under x64, and Mosaic cannot lower an
    int64 -> int32 index conversion (it dies of a ``RecursionError``).
    Passing ``np.int32`` bounds to ``fori_loop`` does not help: the BCD
    kernels still fail the same way in the v5e compile rehearsal
    (``tests/test_tpu_compile.py``).  Same scan, int32 counter.
    """
    def step(carry, _):
        i, x = carry
        return (i + 1, body(i, x)), None

    (_, out), _ = jax.lax.scan(step, (jnp.int32(0), init), None, length=n)
    return out


def block_specs(arrays) -> list:
    """``pl.BlockSpec`` list for the launch, straight from the ArraySpecs."""
    return [pl.BlockSpec(a.block, _int32_index_map(a.index_map))
            for a in arrays]


def out_shapes(arrays) -> list:
    return [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
