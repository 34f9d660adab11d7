"""Estimator-protocol backends over the GPU and TPU analytical models.

GPU configurations are priced in four structural pieces with distinct
sharing behaviour:

  * ``block``   — interior-block footprints, keyed by the *block extent*
    (machine-independent; different (block, folding) pairs fold to the same
    extent).  Computed on the implicit-set path, which the tier-1 property
    tests pin as exactly equal to the enumeration oracle.  Cheap (a handful
    of box unions) — it doubles as the closed-form bound stage of the
    tiered search.
  * ``wave-front`` — wave/layer footprint *volumes* (§4.4 unions): the
    compulsory load/store volumes and the layer-set footprints and
    allocation volumes.  Keyed by extent + machine *geometry* (SM count,
    sector/line size) but not cache sizes, so hypothetical-GPU sweeps
    (e.g. doubled L2) share every count.
  * ``wave-overlap`` — the wave ∩ layer intersection counts (the dominant
    wave-model cost), same key shape as the front.
  * ``walk``    — L1 grid walk + per-warp sector requests, keyed by the full
    (block, folding) launch (machine-independent: shared across machines).
    Both walks read the memoized stream table (gridwalk, DESIGN §10), so
    one address generation per launch serves the whole exact tier — and
    the cache simulator, when a validation pass prices the same launch.

``combine`` then applies capacity hit-rates and limiter arithmetic — the
exact float operations of ``estimate_gpu``, so engine results are bitwise
identical to the direct path.

The tiered bound-then-refine contract (DESIGN.md §5): the bound stage
resolves only the ``block`` task and bounds predicted time below by FP work
and compulsory L2 volume; surviving configurations refine tier by tier
(front → overlap → walk), with ``tier_bound`` tightening at each step —
after the front a sound DRAM bound (realized layer reuse can never exceed
``min(v_comp, r_y*v_y + r_z*v_z)``, the overlaps being disjoint subsets of
the wave footprint), after the overlap the exact DRAM time.  Every bound is
a mathematical lower bound on the model's predicted time; a relative safety
margin of 1e-9 absorbs float-rounding differences between the closed forms
and the model's own arithmetic, so branch-and-bound pruning is exact.

The Pallas backend has the batched path only: one geometry-keyed
structure task per kernel spec, one rate-stage call per group of machines
(``tpu_adapt.pallas_rate_matrix``), entries read from its rows, and VMEM
infeasibility turned into a recorded skip reason.  It has no tiers: its
only bound, for degraded pricing, is the rate stage's own ``hbm_time +
overhead``.
"""
from __future__ import annotations

from repro import obs

from ..access import KernelSpec, LaunchConfig
from ..capacity import CapacityModel
from ..footprint import footprint_bytes
from ..gridwalk import walk_block_l1_fast, warp_sector_requests_fast
from ..machines import GPUGeometry, GPUMachine, TPUGeometry, TPUMachine
from ..perfmodel import (
    L1Parts,
    _interior_block,
    assemble_gpu_estimate,
    dram_front_structure,
    dram_overlap_structure,
    dram_rates,
    gpu_rate_matrix,
    l1_rates,
)
from ..tpu_adapt import vmem_violation
from .protocol import EvalResult, RejectedSpec, Task

# Relative slack applied to the GPU closed-form bounds: the model computes
# times as 1/(bw / volume) while the bounds compute volume/bw directly, which
# can differ by an ulp (~1e-16 relative).  1e-9 is vastly wider than any
# accumulated rounding and vastly tighter than any real pruning margin.
_BOUND_MARGIN = 1.0 - 1e-9


# --------------------------------------------------------------------------
# structural task functions (module-level: picklable for the worker pool)
# --------------------------------------------------------------------------
def _interior_boxes(spec: KernelSpec, launch: LaunchConfig, domain: tuple):
    bidx = _interior_block(launch.grid_for(domain))
    return launch.block_domain_boxes(bidx, domain)


def gpu_block_task(spec: KernelSpec, launch: LaunchConfig, domain: tuple) -> tuple:
    """Interior-block footprints (32B load/store sectors, 128B alloc lines)
    via implicit sets — property-tested equal to the gridwalk oracle."""
    with obs.span("engine.task.footprint", "task"):
        boxes = _interior_boxes(spec, launch, domain)
        return (
            footprint_bytes(spec.loads, boxes, 32),
            footprint_bytes(spec.accesses, boxes, 128),
            footprint_bytes(spec.stores, boxes, 32),
        )


def gpu_walk_task(spec: KernelSpec, launch: LaunchConfig, domain: tuple) -> tuple:
    """L1 bank-conflict cycles + per-warp sector-request upper bound, on the
    vectorized walk (bitwise-equal to the per-warp loop oracle)."""
    with obs.span("engine.task.walk", "task"):
        return (
            walk_block_l1_fast(spec, launch, domain),
            warp_sector_requests_fast(spec, launch, 32, domain),
        )


def gpu_wave_front_task(spec: KernelSpec, launch: LaunchConfig,
                        geometry: GPUGeometry, domain: tuple) -> dict:
    """Wave-model footprint volumes (unions only); the interior-block store
    footprint is fed from the implicit-set path (== oracle) instead of
    re-enumerating.  Takes the machine *geometry*, not the machine: the
    cached value is shared by every rate variant (DESIGN.md §11)."""
    with obs.span("engine.task.wave", "task", part="front"):
        store_bytes = footprint_bytes(
            spec.stores, _interior_boxes(spec, launch, domain),
            geometry.sector_bytes
        )
        return dram_front_structure(spec, launch, geometry, domain,
                                    block_store_bytes=store_bytes)


def gpu_wave_overlap_task(spec: KernelSpec, launch: LaunchConfig,
                          geometry: GPUGeometry, domain: tuple) -> dict:
    """Wave ∩ layer overlap counts — the expensive wave-model intersections."""
    with obs.span("engine.task.wave", "task", part="overlap"):
        return dram_overlap_structure(spec, launch, geometry, domain)


class GPUBackend:
    """Estimator-protocol backend over the multi-limiter GPU model."""

    name = "gpu"

    def __init__(self, spec: KernelSpec, capacity: CapacityModel | None = None,
                 domain: tuple | None = None):
        self.spec = spec
        self.capacity = capacity or CapacityModel()
        self.domain = domain or spec.domain

    def _keys(self, launch: LaunchConfig, machine: GPUMachine) -> tuple:
        """Structural keys (block, front, overlap, walk) — single source of
        truth for task emission, combine lookup, and tier bounds.  Wave keys
        carry the machine's ``GPUGeometry`` (never rate-key fields), so all
        rate variants of one geometry share every entry (DESIGN.md §11)."""
        spec, domain = self.spec, self.domain
        extent = launch.block_extent()
        geom = machine.geometry
        return (
            ("gpu-block", spec, extent, domain),
            ("gpu-wave-front", spec, extent, launch.threads, geom, domain),
            ("gpu-wave-overlap", spec, extent, launch.threads, geom, domain),
            ("gpu-walk", spec, launch.block, launch.folding, domain),
        )

    # items are LaunchConfigs; task order == tier resolution order, so the
    # first failing task yields the same skip reason on both search paths
    def structural_tasks(self, launch: LaunchConfig,
                         machine: GPUMachine) -> list:
        spec, domain = self.spec, self.domain
        geom = machine.geometry
        k_block, k_front, k_overlap, k_walk = self._keys(launch, machine)
        return [
            Task(k_block, gpu_block_task, (spec, launch, domain)),
            Task(k_front, gpu_wave_front_task, (spec, launch, geom, domain)),
            Task(k_overlap, gpu_wave_overlap_task,
                 (spec, launch, geom, domain)),
            Task(k_walk, gpu_walk_task, (spec, launch, domain)),
        ]

    # ---- tiered bound-then-refine (optional protocol methods) ----------
    def bound_tasks(self, launch: LaunchConfig, machine: GPUMachine) -> list:
        """The closed-form bound needs only the (cheap) block footprints."""
        spec, domain = self.spec, self.domain
        k_block = ("gpu-block", spec, launch.block_extent(), domain)
        return [Task(k_block, gpu_block_task, (spec, launch, domain))]

    def tiers(self, launch: LaunchConfig, machine: GPUMachine) -> list:
        """Cheapest discriminating signal first: wave front (sound DRAM
        bound) → wave overlaps (exact DRAM) → grid walk (exact L1/L2)."""
        spec, domain = self.spec, self.domain
        geom = machine.geometry
        _, k_front, k_overlap, k_walk = self._keys(launch, machine)
        return [
            [Task(k_front, gpu_wave_front_task,
                  (spec, launch, geom, domain))],
            [Task(k_overlap, gpu_wave_overlap_task,
                  (spec, launch, geom, domain))],
            [Task(k_walk, gpu_walk_task, (spec, launch, domain))],
        ]

    def tier_bound(self, launch: LaunchConfig, machine: GPUMachine,
                   values: dict) -> float:
        spec = self.spec
        k_block, k_front, k_overlap, _ = self._keys(launch, machine)
        pts = launch.points_per_block()
        # FP work floor (config-independent)
        t = max(spec.flops_per_point, 1e-12) / machine.peak_flops_dp
        if k_block in values:
            # L2 floor: compulsory load sectors + write-through stores; the
            # capacity term of the L1 model only ever adds volume
            v_comp_b, _, v_store_b = values[k_block]
            t = max(t, (v_comp_b + v_store_b) / pts / machine.l2_bw)
        front = values.get(k_front)
        if front is not None:
            if k_overlap in values:
                # exact DRAM time: identical float ops to the model's rate
                struct = dict(front)
                struct.update(values[k_overlap])
                dram = dram_rates(struct, machine, self.capacity)
                vol = dram["load_per_lup"] + dram["store_per_lup"]
                t = max(t, 1.0 / (machine.dram_bw / max(vol, 1e-12)))
            else:
                # sound DRAM floor: realized reuse <= min(v_comp,
                # r_y*v_y + r_z*v_z) because the per-dimension overlaps are
                # disjoint subsets of the wave footprint and hit rates are
                # clamped to [0, 1]
                saved_cap = 0.0
                if front["has_y"]:
                    saved_cap += self.capacity.hit_rate(
                        "l2_over_y", front["alloc_y"], machine.l2_bytes
                    ) * front["v_y"]
                if front["has_z"]:
                    saved_cap += self.capacity.hit_rate(
                        "l2_over_z", front["alloc_z"], machine.l2_bytes
                    ) * front["v_z"]
                saved_cap = min(saved_cap, front["v_comp"])
                v_lb = front["v_comp"] - saved_cap + front["v_store_comp"]
                t = max(t, v_lb / front["wave_pts"] / machine.dram_bw)
        return t * _BOUND_MARGIN

    def primary_time(self, result: EvalResult) -> float:
        return result.estimate.time_per_lup

    def combine(self, launch: LaunchConfig, machine: GPUMachine,
                values: dict) -> tuple:
        spec, domain = self.spec, self.domain
        k_block, k_front, k_overlap, k_walk = self._keys(launch, machine)
        v_comp, v_alloc, v_store = values[k_block]
        cycles, v_up = values[k_walk]
        struct = dict(values[k_front])
        struct.update(values[k_overlap])
        l1 = l1_rates(
            L1Parts(cycles_per_lup=cycles, v_comp=v_comp, v_up=v_up,
                    v_alloc=v_alloc, v_store=v_store),
            launch, machine, self.capacity,
        )
        dram = dram_rates(struct, machine, self.capacity)
        est = assemble_gpu_estimate(spec, launch, machine, domain, l1, dram)
        return launch, est, est.perf_lups, est.limiter

    def sort_key(self, result: EvalResult) -> tuple:
        return (-result.perf,)

    # ---- machine-axis batched evaluation (DESIGN.md §11) ----------------
    def geometry_key(self, machine: GPUMachine) -> GPUGeometry:
        return machine.geometry

    def machine_axis_tasks(self, launch: LaunchConfig,
                           machine: GPUMachine) -> list:
        """Structural work for the whole geometry group — identical to the
        per-machine task set because the keys are already geometry-pure."""
        return self.structural_tasks(launch, machine)

    def batch_order(self, items, values_per_item, machines):
        """Rank every live config on every machine in one array program.

        Returns per-machine index orders into ``items`` (best first, ties
        toward earlier enumeration — matching the scalar ``(-perf, index)``
        sort), per-machine ``(item_pos, reason)`` skip lists (empty: the
        GPU combine has no feasibility constraint), and ``entry(pos, m)``:
        the scalar ``combine`` of a selected entry, so returned estimates
        are bitwise identical to per-machine pricing by construction."""
        import numpy as np

        rep = machines[0]
        parts_list, structs = [], []
        for launch, values in zip(items, values_per_item):
            k_block, k_front, k_overlap, k_walk = self._keys(launch, rep)
            v_comp, v_alloc, v_store = values[k_block]
            cycles, v_up = values[k_walk]
            parts_list.append(L1Parts(
                cycles_per_lup=cycles, v_comp=v_comp, v_up=v_up,
                v_alloc=v_alloc, v_store=v_store))
            struct = dict(values[k_front])
            struct.update(values[k_overlap])
            structs.append(struct)
        perf, _ = gpu_rate_matrix(parts_list, structs, items, rep.geometry,
                                  machines, self.capacity,
                                  self.spec.flops_per_point)
        idx = np.arange(len(items))
        orders = [np.lexsort((idx, -perf[:, m]))
                  for m in range(len(machines))]

        def entry(pos, m):
            return self.combine(items[pos], machines[m], values_per_item[pos])

        return orders, [[] for _ in machines], entry


# --------------------------------------------------------------------------
def pallas_structure_task(spec, geometry: TPUGeometry) -> dict:
    from ..tpu_adapt import pallas_structure

    return pallas_structure(spec, geometry)


class PallasBackend:
    """Estimator backend over the TPU/Pallas analytical model.

    Every Pallas cell is priced batched (DESIGN.md §5, §11): one
    geometry-keyed structure task per candidate, one call of the rate
    stage (``tpu_adapt.pallas_rate_matrix``) per group of machines that
    share a geometry, and entries read from its rows.  Items are
    ``(config_dict, PallasKernelSpec)`` candidates; a ``RejectedSpec``
    (frontend tracing diagnostics) needs no structure and becomes a
    recorded skip, as a VMEM-infeasible row does.
    """

    name = "pallas"

    def geometry_key(self, machine: TPUMachine) -> TPUGeometry:
        return machine.geometry

    def machine_axis_tasks(self, item, machine: TPUMachine) -> list:
        _, spec = item
        if isinstance(spec, RejectedSpec):
            return []
        geom = machine.geometry
        return [Task(("pallas-struct", spec, geom), pallas_structure_task,
                     (spec, geom))]

    @staticmethod
    def _rows(items, values_per_item, machines):
        """Positions of the traced items, their structures, and per machine
        their rate-stage rows (``tpu_adapt.pallas_rate_matrix``) and the
        ``(item_pos, reason)`` skips in item order: tracer rejections and
        VMEM-infeasible rows."""
        from ..tpu_adapt import pallas_rate_matrix

        geom = machines[0].geometry
        live, structs, rejected = [], [], {}
        for pos, ((_, spec), values) in enumerate(zip(items,
                                                      values_per_item)):
            if isinstance(spec, RejectedSpec):
                rejected[pos] = f"SkipConfig: {spec.reason}"
            else:
                live.append(pos)
                structs.append(values[("pallas-struct", spec, geom)])
        rows = pallas_rate_matrix(structs, machines)
        skips = []
        for machine, mrows in zip(machines, rows):
            mskips = dict(rejected)
            for pos, s, row in zip(live, structs, mrows):
                if not row.feasible:
                    mskips[pos] = "SkipConfig: " + vmem_violation(
                        s["vmem_alloc"], s["vmem_reserve"], machine)
            skips.append(sorted(mskips.items()))
        return live, structs, rows, skips

    def batch_order(self, items, values_per_item, machines):
        """Rank every candidate on every machine from one rate-stage call.

        Returns per-machine orders of item positions (by ``(total_time,
        vmem_alloc, index)``, feasible rows only), per-machine
        ``(item_pos, reason)`` skips in item order, and ``entry(pos, m)``:
        ``(config, estimate, perf, limiter)`` read from the rate stage's
        row for item ``pos`` on machine ``m``."""
        from ..tpu_adapt import pallas_estimate

        live, structs, rows, skips = self._rows(items, values_per_item,
                                                machines)
        orders = [
            [live[i] for _, _, i in sorted(
                (row.total, s["vmem_alloc"], i)
                for i, (s, row) in enumerate(zip(structs, mrows))
                if row.feasible)]
            for mrows in rows]
        index = {pos: i for i, pos in enumerate(live)}

        def entry(pos, m):
            config, spec = items[pos]
            i = index[pos]
            est = pallas_estimate(spec.name, structs[i], rows[m][i])
            return config, est, est.work_rate, est.limiter

        return orders, skips, entry

    def batch_bounds(self, items, values_per_item, machine: TPUMachine):
        """Degraded pricing (DESIGN.md §13): each feasible item's HBM-volume
        floor ``hbm_time + overhead``, read from the rate stage (a lower
        bound on its ``total``), as ``(item_pos, bound)`` pairs, plus the
        ``(item_pos, reason)`` skips."""
        live, _, [rows], [skips] = self._rows(items, values_per_item,
                                              [machine])
        return ([(pos, row.hbm_time + row.overhead)
                 for pos, row in zip(live, rows) if row.feasible], skips)
