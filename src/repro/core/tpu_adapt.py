"""TPU-native adaptation of the estimator (DESIGN §2).

On TPU the memory hierarchy is software-managed: a Pallas kernel's
``BlockSpec``s *are* its address expressions — an affine map from grid indices
to HBM block coordinates.  This module prices a Pallas kernel configuration
analytically, before any lowering:

  * **Revisit analysis** (the cache-reuse analogue): Mosaic elides the
    HBM->VMEM copy when an operand's index map yields the same block on
    consecutive grid steps.  For an index map depending on grid dims S under
    lexicographic iteration (last dim fastest), the number of fetches is
    exactly ``prod(grid[0..m])`` with m the innermost dim in S (size>1) —
    derived from counting increment boundaries, and property-tested against
    explicit grid walking.
  * **VMEM footprint**: blocks allocate at (sublane x 128-lane) tile
    granularity — the "wasted cache line" analogue of paper fig. 7 — and
    pipelined operands are double-buffered.  The layer condition of §5.7
    becomes a *hard feasibility constraint*: the working set must fit VMEM.
  * **Issue model**: MXU matmuls pay padding to 128x128 systolic tiles (the
    TPU analogue of L1 wavefront efficiency); VPU ops pay (8,128) vector-tile
    padding.
  * **Multi-limiter time**: with Mosaic's double-buffered pipeline, compute
    overlaps DMA, so T = max(T_mxu+T_vpu, T_hbm, T_vmem) + grid overhead.

The model runs in two stages: ``pallas_structure`` (counts, keyed by tile
geometry) and ``pallas_rate_matrix`` (the formula above, over candidates x
machines, written nowhere else).  ``estimate_pallas`` is one row of it.

``select_pallas_config`` ranks candidate block configurations — replacing
autotuning exactly as the paper does for thread-block sizes.
"""
from __future__ import annotations

import math
from collections import namedtuple
from dataclasses import dataclass, field as dc_field
from typing import Callable, Iterable, Sequence

from .access import memoize_hash
from .machines import TPUMachine, TPU_V5E


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


# VMEM Mosaic keeps beside a kernel's blocks and scratch (``vmem_reserve``):
# working copies of what the body reads (relayouts of unaligned windows,
# concatenations) -- at most one copy of the input blocks and scratch -- or
# the f32 results of its dots, whichever is larger, plus this much of its
# own.  Calibrated against compiles for a described v5e at the six
# generators' real sizes (JAX 0.9.0, libtpu 0.0.34), bisecting the least
# limit that compiles: stencil ring needs 10.1 MiB beyond its footprint
# (bound 21.5), stencil replane 11.7 (17.7), flash attention (1024, 2048)
# 8.9 (12.5), ytile_ring ty=256 8.1 (16.9), matmul 1024x2048x1024 4.5
# (16.0), LBM replane 3.9 (17.5); jacobi ty=1024 compiles at no limit and
# is skipped.  ``python -m repro.kernels.compile_probe`` checks every
# candidate against the compiler.
VMEM_BASE_RESERVE_BYTES = 4 * 1024 * 1024


@memoize_hash
@dataclass(frozen=True)
class OperandSpec:
    """One Pallas operand: its BlockSpec as seen by the estimator.

    ``grid_deps``: grid dims (indices into the kernel grid) the index map
    depends on.  ``revisit=False`` forces per-step refetch (e.g. dynamic,
    data-dependent index maps where Mosaic cannot prove equality).
    ``fetches``: exact fetch count where the closed form over ``grid_deps``
    over-counts (two grid dims stepping one block coordinate, see
    ``linear_fetch_count``); ``None`` means the closed form is exact.
    """

    name: str
    block_shape: tuple
    elem_bytes: int = 4
    grid_deps: tuple = ()
    is_output: bool = False
    n_buffers: int = 2          # double-buffered pipeline default
    revisit: bool = True
    fetches: int | None = None

    def block_bytes(self) -> int:
        return math.prod(self.block_shape) * self.elem_bytes

    def vmem_block_bytes(self, machine: TPUMachine) -> int:
        """Allocated bytes: trailing dims padded to the (sublane, lane) tile."""
        shape = list(self.block_shape)
        if len(shape) >= 1:
            shape[-1] = _roundup(shape[-1], machine.vpu_lanes)
        if len(shape) >= 2:
            shape[-2] = _roundup(shape[-2], machine.sublane_elems(self.elem_bytes))
        return math.prod(shape) * self.elem_bytes


@memoize_hash
@dataclass(frozen=True)
class MatmulShape:
    m: int
    k: int
    n: int

    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n

    def padded_flops(self, machine: TPUMachine, elem_bytes: int = 2) -> float:
        sub = machine.sublane_elems(elem_bytes)
        return (
            2.0
            * _roundup(self.m, sub)
            * _roundup(self.k, machine.mxu_dim)
            * _roundup(self.n, machine.mxu_dim)
        )


@memoize_hash
@dataclass(frozen=True)
class PallasKernelSpec:
    """Estimator view of one pallas_call configuration."""

    name: str
    grid: tuple
    operands: tuple                      # tuple[OperandSpec, ...]
    matmuls_per_step: tuple = ()         # tuple[MatmulShape, ...]
    vpu_elems_per_step: float = 0.0      # elementwise VPU element-ops per step
    vpu_shape: tuple = ()                # representative (sub, lane) shape for padding
    scratch_bytes: int = 0
    work_per_step: float = 1.0           # work units (points/tokens) per grid step
    elem_bytes: int = 4                  # dominant compute dtype


def fetch_count(grid: tuple, grid_deps: tuple, revisit: bool = True) -> int:
    """Fetches under lexicographic grid iteration with consecutive-step
    copy elision (see module docstring)."""
    n_steps = math.prod(grid) if grid else 1
    deps = [d for d in grid_deps if grid[d] > 1]
    if not revisit:
        return n_steps
    if not deps:
        return 1
    m = max(deps)
    out = 1
    for d in range(m + 1):
        out *= grid[d]
    return out


def linear_fetch_count(grid: tuple, coeffs) -> int:
    """Exact fetches for a purely linear index map, ``coeffs[j][d]`` being
    grid dim d's coefficient in block coordinate j.

    The step that increments dim d resets every dim e > d, so it moves
    coordinate j by ``c[j][d] - sum_{e>d} c[j][e] * (grid[e] - 1)``
    wherever it happens, and it happens ``prod(grid[:d]) * (grid[d] - 1)``
    times.  Steps that move no coordinate are elided.  Equals
    ``fetch_count`` unless two grid dims step one coordinate.
    """
    n = 1
    for d, g in enumerate(grid):
        moves = any(
            row[d] - sum(row[e] * (grid[e] - 1) for e in range(d + 1, len(grid)))
            for row in coeffs)
        if g > 1 and moves:
            n += math.prod(grid[:d]) * (g - 1)
    return n


def fetch_count_oracle(grid: tuple, index_map: Callable, revisit: bool = True) -> int:
    """Explicit grid walk (the listing-5 analogue for TPU) — test oracle."""
    from itertools import product

    steps = list(product(*[range(g) for g in grid]))
    if not steps:
        return 0
    count = 0
    prev = object()
    for s in steps:
        cur = index_map(*s)
        if not revisit or cur != prev:
            count += 1
        prev = cur
    return count


@dataclass
class PallasEstimate:
    kernel: str
    hbm_bytes: float
    hbm_time: float
    mxu_time: float
    vpu_time: float
    vmem_time: float
    vmem_alloc_bytes: int
    grid_overhead: float
    total_time: float
    limiter: str
    feasible: bool
    work: float
    detail: dict = dc_field(default_factory=dict)

    @property
    def work_rate(self) -> float:
        return self.work / self.total_time if self.total_time > 0 else 0.0

    @property
    def bytes_per_work(self) -> float:
        return self.hbm_bytes / self.work if self.work else 0.0


def hbm_traffic(spec: PallasKernelSpec) -> tuple:
    """HBM traffic via revisit analysis: ``(hbm_bytes, per_operand detail)``,
    closed-form BlockSpec byte counting."""
    hbm_bytes = 0.0
    per_op = {}
    for op in spec.operands:
        fetches = op.fetches if op.fetches is not None else \
            fetch_count(spec.grid, op.grid_deps, op.revisit)
        # short-row DMA efficiency: rows shorter than the 256B granule waste bw
        row_bytes = op.block_shape[-1] * op.elem_bytes if op.block_shape else op.elem_bytes
        eff = min(1.0, row_bytes / 256.0) if row_bytes < 256 else 1.0
        vol = fetches * op.block_bytes()
        per_op[op.name] = {"fetches": fetches, "bytes": vol, "dma_eff": eff}
        hbm_bytes += vol / max(eff, 1e-6)
    return hbm_bytes, per_op


def vmem_footprint(operands, scratch_bytes: int, geometry) -> int:
    """VMEM a pallas_call allocates, as the feasibility check counts it:
    scratch plus every pipelined block padded to the (sublane, lane) tile,
    times its buffer count."""
    alloc = scratch_bytes
    for op in operands:
        alloc += op.vmem_block_bytes(geometry) * op.n_buffers
    return alloc


def vmem_reserve(operands, scratch_bytes: int, dots, geometry) -> int:
    """VMEM Mosaic needs beyond ``vmem_footprint``: the larger of one copy
    of the input blocks and scratch, and the f32 results of the step's
    dots (``(m, n)`` shapes), plus ``VMEM_BASE_RESERVE_BYTES``."""
    reads = scratch_bytes + sum(op.vmem_block_bytes(geometry)
                                for op in operands if not op.is_output)
    results = sum(OperandSpec("", tuple(d), 4).vmem_block_bytes(geometry)
                  for d in dots)
    return VMEM_BASE_RESERVE_BYTES + max(reads, results)


def vmem_limit_bytes(operands, scratch_bytes: int, dots=(),
                     machine: TPUMachine = TPU_V5E) -> int:
    """Mosaic's scoped-VMEM limit for a kernel: its footprint plus its
    reserve, capped at the machine's VMEM.  Exactly the room the
    feasibility check requires to fit, so a candidate it passes gets the
    room it was priced with."""
    need = vmem_footprint(operands, scratch_bytes, machine) \
        + vmem_reserve(operands, scratch_bytes, dots, machine)
    return min(machine.vmem_bytes, need)


def spec_vmem_reserve(spec: PallasKernelSpec, geometry) -> int:
    """``vmem_reserve`` of a spec: its dots are its per-step matmuls."""
    return vmem_reserve(spec.operands, spec.scratch_bytes,
                        [(m.m, m.n) for m in spec.matmuls_per_step], geometry)


def vmem_violation(vmem_alloc, vmem_reserve_bytes, machine) -> str:
    """Skip reason for a candidate whose footprint plus reserve exceeds the
    machine's VMEM."""
    return (f"VMEM layer condition violated: {vmem_alloc} B allocated + "
            f"{vmem_reserve_bytes} B compiler reserve > "
            f"{machine.vmem_bytes} B VMEM")


def pallas_structure(spec: PallasKernelSpec, geometry) -> dict:
    """Geometry-keyed structural stage of the Pallas model (DESIGN.md §11).

    ``geometry`` is a ``TPUGeometry`` (or any object with ``vpu_lanes``,
    ``sublane_elems``, ``mxu_dim``) — everything here depends on tile
    paddings and the grid, never on bandwidths, FLOP peaks, or the VMEM
    *capacity* budget, so all rate variants of one geometry share this
    computation.  ``pallas_rate_matrix`` turns it into times.
    """
    n_steps = math.prod(spec.grid) if spec.grid else 1
    hbm_bytes, per_op = hbm_traffic(spec)
    vmem_alloc = vmem_footprint(spec.operands, spec.scratch_bytes, geometry)
    vmem_reserve_bytes = spec_vmem_reserve(spec, geometry)
    mxu_flops = sum(m.padded_flops(geometry, spec.elem_bytes)
                    for m in spec.matmuls_per_step)
    vpu_elems = spec.vpu_elems_per_step
    if spec.vpu_shape and len(spec.vpu_shape) >= 2:
        sub = geometry.sublane_elems(spec.elem_bytes)
        pad = (
            _roundup(spec.vpu_shape[-2], sub)
            * _roundup(spec.vpu_shape[-1], geometry.vpu_lanes)
        ) / max(spec.vpu_shape[-2] * spec.vpu_shape[-1], 1)
        vpu_elems *= pad
    vmem_touch = sum(op.block_bytes() for op in spec.operands) * n_steps
    return {
        "n_steps": n_steps,
        "hbm_bytes": hbm_bytes,
        "per_op": per_op,
        "vmem_alloc": vmem_alloc,
        "vmem_reserve": vmem_reserve_bytes,
        "mxu_flops": mxu_flops,
        "vpu_elems": vpu_elems,
        "vmem_touch": vmem_touch,
        "work": spec.work_per_step * n_steps,
        "elem_bytes": spec.elem_bytes,
    }


PALLAS_LIMITERS = ("MXU", "VPU", "HBM", "VMEM")

# one candidate on one machine, as the rate stage prices it
PallasRow = namedtuple("PallasRow", (
    "hbm_time", "mxu_time", "vpu_time", "vmem_time", "overhead", "total",
    "limiter", "feasible"))


def pallas_rate_matrix(structs, machines) -> list:
    """The rate stage of the TPU model over ``(candidates x machines)``,
    the one place its formula is written (DESIGN.md §2, §11):

        T = max(T_mxu + T_vpu, T_hbm, T_vmem) + n_steps * overhead

    Returns per machine one ``PallasRow`` per structure: the terms, their
    ``total``, the ``limiter`` index into ``PALLAS_LIMITERS`` and VMEM
    residency (``feasible``).  Among equal maxima the limiter is the last
    of compute, HBM, VMEM; compute is MXU unless VPU time exceeds MXU
    time.  ``hbm_time + overhead`` is the HBM-volume floor under ``total``
    (``max`` and ``+`` are monotone in IEEE arithmetic).  Plain Python: a
    generator's space on a handful of machines is a few hundred pairs at
    most, where numpy's per-call cost exceeds the arithmetic.
    """
    rows = []
    for m in machines:
        mrows = []
        for s in structs:
            n = s["n_steps"]
            hbm = s["hbm_bytes"] / m.hbm_bw
            mxu = n * s["mxu_flops"] / m.peak_flops(s["elem_bytes"])
            vpu = n * s["vpu_elems"] / m.vpu_flops
            vmem = s["vmem_touch"] / m.vmem_bw
            compute = mxu + vpu
            overhead = n * m.grid_step_overhead_s
            limiter = (3 if vmem >= max(compute, hbm) else
                       2 if hbm >= compute else 0 if mxu >= vpu else 1)
            mrows.append(PallasRow(
                hbm, mxu, vpu, vmem, overhead,
                max(compute, hbm, vmem) + overhead, limiter,
                s["vmem_alloc"] + s["vmem_reserve"] <= m.vmem_bytes))
        rows.append(mrows)
    return rows


def pallas_estimate(name: str, struct: dict, row: PallasRow) -> PallasEstimate:
    """The estimate of one candidate from its structure and its row."""
    return PallasEstimate(
        kernel=name,
        hbm_bytes=struct["hbm_bytes"],
        hbm_time=row.hbm_time,
        mxu_time=row.mxu_time,
        vpu_time=row.vpu_time,
        vmem_time=row.vmem_time,
        vmem_alloc_bytes=struct["vmem_alloc"],
        grid_overhead=row.overhead,
        total_time=row.total,
        limiter=PALLAS_LIMITERS[row.limiter],
        feasible=row.feasible,
        work=struct["work"],
        detail={"per_operand": struct["per_op"],
                "n_steps": struct["n_steps"],
                "vmem_reserve": struct["vmem_reserve"]},
    )


def estimate_pallas(spec: PallasKernelSpec,
                    machine: TPUMachine = TPU_V5E) -> PallasEstimate:
    """One spec on one machine: its structure, then one row of the rate
    stage."""
    struct = pallas_structure(spec, machine.geometry)
    [[row]] = pallas_rate_matrix([struct], [machine])
    return pallas_estimate(spec.name, struct, row)


@dataclass
class RankedPallasConfig:
    config: dict
    spec: PallasKernelSpec
    estimate: PallasEstimate


def select_pallas_config(
    candidates: Iterable[tuple],
    machine: TPUMachine = TPU_V5E,
    top_k: int | None = None,
    engine=None,
) -> list[RankedPallasConfig]:
    """Rank (config_dict, PallasKernelSpec) candidates by predicted time.

    Routes through the exploration engine (``repro.core.engine``), which
    memoizes per-spec structures across sweeps and prices every candidate
    in one rate-stage call: infeasible candidates (VMEM oversubscription —
    the violated layer condition) are recorded in the engine report's
    ``skipped`` list with their reason; ties break toward smaller VMEM
    footprints.  Pass an ``Explorer`` as ``engine`` to share its cache
    across calls.  ``top_k`` keeps the exhaustive ranking's first k; no
    candidate is pruned (``report.pruned`` stays empty), and infeasible
    candidates beyond the first k are still recorded in ``skipped``.
    """
    from .engine import Explorer

    candidates = list(candidates)
    explorer = engine or Explorer()
    report = explorer._rank_pallas(candidates, machine, top_k=top_k)
    ranked = [
        RankedPallasConfig(r.config, candidates[r.index][1], r.estimate)
        for r in report.entries
    ]
    return ranked[:top_k] if top_k else ranked


def pow2_tiles(lo: int, hi: int) -> list[int]:
    out = []
    t = lo
    while t <= hi:
        out.append(t)
        t *= 2
    return out
