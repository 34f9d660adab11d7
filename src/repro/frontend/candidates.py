"""Decision-space sweeps over parameterized kernel builders.

The four kernel generators used to hand-enumerate their (variant × tile)
decision spaces *and* hand-write the spec for each point — dozens of
``OperandSpec`` lines per kernel, kept in sync with the kernel code by eye.
:func:`candidates` replaces that: a generator supplies one ``build(config)``
callback returning a :class:`KernelBuild` (the kernel's calling convention,
placeholder args, and cost annotations), and the frontend traces each
configuration into its spec mechanically.

Configurations the tracer rejects, and those whose blocks Mosaic cannot
tile, yield ``(config, RejectedSpec(reason))`` pairs: the exploration
engine's Pallas backend resolves those to ``report.skipped`` entries
carrying the diagnostic, so a non-affine kernel shows up as an actionable
skip reason in the ranking report instead of an exception mid-sweep.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Iterable, Iterator

from repro.core.engine.protocol import RejectedSpec
from repro.core.machines import TPU_V5E

from .lower import CostModel, lower_tpu
from .trace import TraceError, trace_kernel


@dataclass
class KernelBuild:
    """One configuration of a kernel builder, ready to trace."""

    call: Callable                    # the pallas-call closure to trace
    args: tuple                       # trace.arg placeholders, by position
    name: str = "kernel"
    costs: CostModel | None = None
    operand_names: tuple | None = None
    out_names: tuple | None = None
    trace_body: bool = False

    def trace(self):
        return trace_kernel(
            self.call, self.args, name=self.name,
            operand_names=self.operand_names, out_names=self.out_names,
            trace_body=self.trace_body)


def candidates(build: Callable, space: Iterable,
               skip_build_errors: tuple = (ValueError,)) -> Iterator[tuple]:
    """Yield ``(config, PallasKernelSpec | RejectedSpec)`` for each config.

    ``build(config)`` returns a :class:`KernelBuild` (or ``None`` to drop a
    configuration silently, e.g. a non-dividing tile).  Builder exceptions
    in ``skip_build_errors`` and tracer rejections become ``RejectedSpec``
    entries instead of aborting the sweep.
    """
    for config in space:
        try:
            kb = build(config)
        except skip_build_errors as e:
            yield config, RejectedSpec(str(config), f"build failed: {e}")
            continue
        if kb is None:
            continue
        try:
            traced = kb.trace()
            spec = lower_tpu(traced, kb.costs, name=kb.name)
        except TraceError as e:
            yield config, RejectedSpec(kb.name, str(e))
            continue
        untileable = _untileable_block(traced)
        if untileable:
            yield config, RejectedSpec(kb.name, untileable)
            continue
        yield config, spec


def _untileable_block(traced, machine=TPU_V5E) -> str | None:
    """Mosaic's block rule: each of the last two block dims divides by its
    tile (sublanes, lanes) or equals the array's own dim.  The tile is the
    same for every dtype: compile probes on v5e accept bf16 blocks of 8
    rows.  A candidate that breaks it cannot compile, so it is no
    candidate."""
    tile = (machine.vpu_sublanes, machine.vpu_lanes)
    for op in traced.operands:
        pairs = zip(op.block_shape[-2:], op.arg_shape[-2:],
                    tile[-len(op.block_shape):])
        if any(b % t and b != n for b, n, t in pairs):
            return (f"operand {op.name!r}: block {op.block_shape} of array "
                    f"{op.arg_shape} does not tile on TPU (the last two "
                    f"block dims must divide by {tile} or equal the "
                    f"array's)")
    return None


def grid_space(**axes) -> Iterator[dict]:
    """Cartesian decision space: ``grid_space(bm=[128, 256], bn=[128])``
    yields config dicts in row-major order with the given key order."""
    keys = list(axes)
    vals = [list(axes[k]) for k in keys]

    def rec(i, acc):
        if i == len(keys):
            yield dict(acc)
            return
        for v in vals[i]:
            acc.append((keys[i], v))
            yield from rec(i + 1, acc)
            acc.pop()

    yield from rec(0, [])
