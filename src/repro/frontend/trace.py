"""Symbolic tracing of Pallas kernels into address-expression artifacts.

``trace_kernel`` runs a kernel *builder* (the ``call`` closure a
``make_<kernel>`` factory returns) with shape-only placeholder arguments
inside a patch context that intercepts ``pl.pallas_call``.  Nothing is
compiled and no arrays are materialized; instead the trace captures the one
artifact the estimator requires from a code generator (paper §1.2):

  * the launch structure — grid, BlockSpecs, out shapes, scratch;
  * per operand, the **address expression**: the BlockSpec index map
    evaluated over symbolic grid coordinates (``affine.Sym``), from which
    grid dependence, revisit behaviour, and HBM volumes follow exactly;
  * optionally (``trace_body=True``) the kernel body's ref-indexing
    accesses over symbolic coordinates, plus elementwise-op and matmul
    counts — enough to lower thread-level affine maps for the GPU
    estimator and to derive default cost models.

Kernels outside the affine contract are rejected with a precise diagnostic
naming the offending access (``TraceError``), which the exploration engine
surfaces as an actionable ``report.skipped`` reason rather than a crash.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field as dc_field

import numpy as np

from .affine import (
    AffineExpr,
    NonAffineError,
    Sym,
    SymPredicate,
    affine,
)


class TraceError(RuntimeError):
    """A kernel (or one access of it) is outside the traceable contract."""

    def __init__(self, kernel: str, where: str, reason: str):
        self.kernel = kernel
        self.where = where
        self.reason = reason
        super().__init__(f"{kernel}: {where}: {reason}")


@dataclass(frozen=True)
class Placeholder:
    """Shape/dtype stand-in for one kernel-builder argument."""

    name: str
    shape: tuple
    dtype: object = np.float32

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def elem_bytes(self) -> int:
        return int(np.dtype(self.dtype).itemsize)


def arg(name: str, shape, dtype=np.float32) -> Placeholder:
    """Declare a traced-kernel argument (mirrors jax.ShapeDtypeStruct)."""
    return Placeholder(name, tuple(int(s) for s in shape), dtype)


def grid_sym(d: int) -> Sym:
    """The canonical symbol for grid dimension ``d``."""
    return Sym(f"g{d}")


# --------------------------------------------------------------------------
# trace result structures
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class TracedOperand:
    """One pallas operand with its evaluated address expression."""

    name: str
    block_shape: tuple
    elem_bytes: int
    index_exprs: tuple          # per block dim: AffineExpr over grid syms
    grid_deps: tuple            # grid dims the index map depends on
    is_output: bool
    arg_name: str               # underlying array argument
    arg_shape: tuple            # full array shape (field size)
    arg_pos: int                # identity of the underlying argument


@dataclass(frozen=True)
class TracedScratch:
    shape: tuple
    elem_bytes: int

    def nbytes(self) -> int:
        return math.prod(self.shape) * self.elem_bytes


@dataclass
class BodyAccess:
    """One load/store the kernel body performed, in block coordinates."""

    ref_kind: str               # "op" | "scratch"
    ref_index: int
    offsets: tuple              # per ref dim: AffineExpr | int
    extents: tuple              # per ref dim: int
    is_store: bool = False


@dataclass
class BodyMatmul:
    m: int
    k: int
    n: int
    lhs: BodyAccess | None = None
    rhs: BodyAccess | None = None


@dataclass
class TracedBody:
    """Digest of one symbolic kernel-body execution."""

    ok: bool = False
    error: str | None = None
    accesses: list = dc_field(default_factory=list)   # ordered BodyAccess
    matmuls: list = dc_field(default_factory=list)    # ordered BodyMatmul
    elementwise_elems: float = 0.0
    notes: list = dc_field(default_factory=list)

    def loads(self, kind: str | None = None):
        return [a for a in self.accesses
                if not a.is_store and (kind is None or a.ref_kind == kind)]

    def stores(self, kind: str | None = None):
        return [a for a in self.accesses
                if a.is_store and (kind is None or a.ref_kind == kind)]

    def scratch_accesses(self):
        return [a for a in self.accesses if a.ref_kind == "scratch"]


@dataclass
class TracedKernel:
    """Everything ``trace_kernel`` extracted from one pallas_call."""

    name: str
    grid: tuple
    operands: tuple             # tuple[TracedOperand, ...], inputs then outputs
    scratch: tuple              # tuple[TracedScratch, ...]
    body: TracedBody

    @property
    def inputs(self):
        return tuple(o for o in self.operands if not o.is_output)

    @property
    def outputs(self):
        return tuple(o for o in self.operands if o.is_output)

    def scratch_bytes(self) -> int:
        return sum(s.nbytes() for s in self.scratch)

    def points_per_step(self) -> int:
        """Output elements written per grid step (work-unit default)."""
        return sum(math.prod(o.block_shape) for o in self.outputs)


# --------------------------------------------------------------------------
# symbolic body values
# --------------------------------------------------------------------------
@dataclass
class _View:
    """A rectangular window of a ref: offsets/extents per ref dim, plus the
    (possibly permuted) subset of ref dims the array axes map to."""

    ref: "_TracedRef"
    offsets: tuple
    extents: tuple
    dims: tuple                 # array axis -> ref dim

    def array_shape(self) -> tuple:
        return tuple(self.extents[d] for d in self.dims)


class SymArray:
    """Shape/dtype-tracking stand-in for an intermediate jnp array."""

    def __init__(self, shape, dtype, view: _View | None = None, ctx=None):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype
        self.view = view
        self.ctx = ctx

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def T(self):
        return _transpose(self, None)

    def astype(self, dtype):
        # pure cast: keep the view so consumption records the right access
        return SymArray(self.shape, dtype, self.view, self.ctx)

    # ---- arithmetic ----------------------------------------------------
    def _binop(self, other, count: bool = True):
        ctx = self.ctx or getattr(other, "ctx", None)
        shapes = [self.shape]
        ctx._consume(self)
        if isinstance(other, SymArray):
            ctx._consume(other)
            shapes.append(other.shape)
        elif isinstance(other, (AffineExpr, Sym, SymPredicate)):
            pass                      # scalar symbolic index value
        elif hasattr(other, "shape"):
            shapes.append(tuple(other.shape))
        out_shape = np.broadcast_shapes(*shapes)
        if count:
            ctx.body.elementwise_elems += float(math.prod(out_shape) or 1)
        return SymArray(out_shape, self.dtype, None, ctx)

    def __add__(self, other):
        return self._binop(other)

    __radd__ = __add__
    __sub__ = __add__
    __rsub__ = __add__
    __mul__ = __add__
    __rmul__ = __add__
    __truediv__ = __add__
    __rtruediv__ = __add__
    __pow__ = __add__

    def __neg__(self):
        return self._binop(0.0)

    # comparisons produce mask arrays (no flop accounting)
    def _cmp(self, other):
        return self._binop(other, count=False)

    __lt__ = _cmp
    __le__ = _cmp
    __gt__ = _cmp
    __ge__ = _cmp
    __eq__ = _cmp          # elementwise, like jnp
    __ne__ = _cmp
    __hash__ = None

    def __matmul__(self, other):
        return _record_matmul(self.ctx, self, other)

    # ---- reductions ----------------------------------------------------
    def _reduce(self, axis=None, keepdims=False):
        ctx = self.ctx
        ctx._consume(self)
        ctx.body.elementwise_elems += float(math.prod(self.shape) or 1)
        if axis is None:
            shape = (1,) * self.ndim if keepdims else ()
        else:
            axes = {a % self.ndim for a in
                    (axis if isinstance(axis, tuple) else (axis,))}
            shape = tuple(
                1 if i in axes else s
                for i, s in enumerate(self.shape)
                if keepdims or i not in axes)
        return SymArray(shape, self.dtype, None, ctx)

    def sum(self, axis=None, keepdims=False):
        return self._reduce(axis, keepdims)

    max = sum
    min = sum
    mean = sum

    def __getitem__(self, idx):
        """Static unit-stride slice of a loaded value: a narrower window of
        the same ref, or of a derived array."""
        ctx = self.ctx
        if not isinstance(idx, tuple):
            idx = (idx,)
        idx = idx + (slice(None),) * (self.ndim - len(idx))
        if len(idx) != self.ndim or any(
                not isinstance(i, slice) or i.step not in (None, 1)
                for i in idx):
            raise TraceError(ctx.name, "value slice",
                             f"only static unit-stride slices of a loaded "
                             f"value are traceable, got {idx!r}")
        bounds = [i.indices(size)[:2] for i, size in zip(idx, self.shape)]
        shape = tuple(stop - start for start, stop in bounds)
        if self.view is None:
            ctx.body.notes.append(
                "slice of a derived (non-ref) array: per-point address "
                "expressions unavailable for it")
            return SymArray(shape, self.dtype, None, ctx)
        v = self.view
        offsets, extents = list(v.offsets), list(v.extents)
        for d, (start, _stop), n in zip(v.dims, bounds, shape):
            offsets[d] = offsets[d] + start
            extents[d] = n
        view = _View(v.ref, tuple(offsets), tuple(extents), v.dims)
        return SymArray(shape, self.dtype, view, ctx)

    def __bool__(self):
        raise NonAffineError(
            "traced array used as a concrete bool (data-dependent control "
            "flow is not traceable)")

    def __repr__(self):
        return f"SymArray(shape={self.shape}, view={self.view is not None})"


class _TracedRef:
    """Symbolic stand-in for a pallas Ref (operand or scratch buffer)."""

    def __init__(self, ctx, kind: str, index: int, name: str, shape, dtype):
        self.ctx = ctx
        self.kind = kind
        self.index = index
        self.name = name
        self.shape = tuple(shape)
        self.dtype = dtype

    @property
    def ndim(self):
        return len(self.shape)

    def _window(self, idx):
        """Parse a ref index into (offsets, extents, kept dims)."""
        if not isinstance(idx, tuple):
            idx = (idx,)
        if any(i is Ellipsis for i in idx):
            pos = idx.index(Ellipsis)
            fill = self.ndim - (len(idx) - 1)
            idx = idx[:pos] + (slice(None),) * fill + idx[pos + 1:]
        idx = idx + (slice(None),) * (self.ndim - len(idx))
        if len(idx) > self.ndim:
            raise TraceError(self.ctx.name, f"ref {self.name!r}",
                             f"too many indices {idx!r} for shape {self.shape}")
        offsets, extents, dims = [], [], []
        for d, (i, size) in enumerate(zip(idx, self.shape)):
            if isinstance(i, slice):
                if i.step not in (None, 1):
                    raise TraceError(self.ctx.name, f"ref {self.name!r}",
                                     f"strided ref slice {i!r} is not affine")
                start = 0 if i.start is None else int(i.start)
                stop = size if i.stop is None else int(i.stop)
                # numpy slice semantics: negative bounds count from the end
                if start < 0:
                    start += size
                if stop < 0:
                    stop += size
                start = min(max(start, 0), size)
                stop = min(max(stop, 0), size)
                if stop <= start:
                    raise TraceError(
                        self.ctx.name, f"ref {self.name!r}",
                        f"empty ref slice {i!r} on dim {d} (size {size})")
                offsets.append(start)
                extents.append(stop - start)
                dims.append(d)
            else:
                if isinstance(i, (int, np.integer)) and i < 0:
                    i += size  # numpy semantics: index from the end
                if isinstance(i, SymArray):
                    raise TraceError(
                        self.ctx.name, f"ref {self.name!r}",
                        "indexed by a traced array value (data-dependent "
                        "addressing is not an affine address expression)")
                try:
                    off = affine(i) if not isinstance(i, (int, np.integer)) \
                        else int(i)
                except NonAffineError as e:
                    raise TraceError(self.ctx.name, f"ref {self.name!r}",
                                     f"non-affine index: {e}") from e
                offsets.append(off)
                extents.append(1)
        return tuple(offsets), tuple(extents), tuple(dims)

    def __getitem__(self, idx):
        offsets, extents, dims = self._window(idx)
        view = _View(self, offsets, extents, dims)
        return SymArray(view.array_shape(), self.dtype, view, self.ctx)

    def __setitem__(self, idx, value):
        offsets, extents, dims = self._window(idx)
        if isinstance(value, SymArray):
            self.ctx._consume(value)
        self.ctx._record(BodyAccess(self.kind, self.index, offsets, extents,
                                    is_store=True))

    def __repr__(self):
        return f"Ref({self.name}, {self.shape})"


def _transpose(x: SymArray, axes):
    if axes is None:
        axes = tuple(reversed(range(x.ndim)))
    axes = tuple(a % x.ndim for a in axes)
    shape = tuple(x.shape[a] for a in axes)
    view = None
    if x.view is not None:
        view = _View(x.view.ref, x.view.offsets, x.view.extents,
                     tuple(x.view.dims[a] for a in axes))
    return SymArray(shape, x.dtype, view, x.ctx)


def _record_matmul(ctx, a, b):
    for side, v in (("lhs", a), ("rhs", b)):
        if not isinstance(v, SymArray):
            raise TraceError(ctx.name, "matmul",
                             f"{side} is not a traced array: {v!r}")
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise TraceError(ctx.name, "matmul",
                         f"unsupported shapes {a.shape} @ {b.shape}")
    lhs = ctx._consume(a)
    rhs = ctx._consume(b)
    m, k = a.shape
    n = b.shape[1]
    ctx.body.matmuls.append(BodyMatmul(m, k, n, lhs, rhs))
    return SymArray((m, n), np.float32, None, ctx)


def _access_of(view: _View) -> BodyAccess:
    return BodyAccess(view.ref.kind, view.ref.index, view.offsets,
                      view.extents)


# --------------------------------------------------------------------------
# the trace context: pallas_call capture + patched jnp/lax surface
# --------------------------------------------------------------------------
class _Trace:
    def __init__(self, name: str, args):
        self.name = name
        self.args = args                      # Placeholders (by position)
        self.captured = None                  # dict of pallas_call pieces
        self.body = TracedBody()
        self._seen = set()
        self.body_active = False

    # ---- body recording ------------------------------------------------
    def _record(self, access: BodyAccess) -> BodyAccess:
        key = (access.ref_kind, access.ref_index,
               tuple(_off_key(o) for o in access.offsets),
               access.extents, access.is_store)
        if key not in self._seen:
            self._seen.add(key)
            self.body.accesses.append(access)
        return access

    def _consume(self, x) -> BodyAccess | None:
        """Record the load behind a view-backed array, once per window."""
        if isinstance(x, SymArray) and x.view is not None:
            return self._record(_access_of(x.view))
        return None

    # ---- pallas_call capture -------------------------------------------
    def capture(self, kernel, grid, in_specs, out_specs, out_shape,
                scratch_shapes):
        if self.captured is not None:
            raise TraceError(self.name, "pallas_call",
                             "builder invoked pallas_call more than once "
                             "(trace one kernel per builder)")
        self.captured = dict(kernel=kernel, grid=grid, in_specs=in_specs,
                             out_specs=out_specs, out_shape=out_shape,
                             scratch_shapes=scratch_shapes)


def _off_key(o):
    return o._key() if isinstance(o, AffineExpr) else int(o)


class _TracedOutput:
    """Placeholder for a traced pallas_call's result.

    Builders must return the pallas output unmodified — post-processing
    (cropping padding, reshaping) belongs outside the traced builder, where
    real arrays exist (see ``kernels/transpose_pad/ops.py``).  Any attempt
    to compute with this placeholder explains that contract instead of
    failing with a bare TypeError deep inside jax.
    """

    def __init__(self, kernel_name: str, shape, dtype):
        self.shape = tuple(shape)
        self.dtype = dtype
        self._kernel = kernel_name

    def _contract(self, what: str):
        raise TraceError(
            self._kernel, "builder",
            f"the builder {what} the pallas_call result; traced builders "
            f"must return it unmodified — move post-processing (cropping, "
            f"reshaping, arithmetic) outside the traced closure")

    def __getitem__(self, _idx):
        self._contract("slices")

    def __iter__(self):
        self._contract("iterates over")

    def _arith(self, *_a, **_k):
        self._contract("computes with")

    __add__ = __radd__ = __sub__ = __rsub__ = _arith
    __mul__ = __rmul__ = __truediv__ = __rtruediv__ = _arith
    __matmul__ = __neg__ = __array__ = _arith


_CTX: _Trace | None = None


def _sym_args(*vals):
    from .affine import is_symbolic

    for v in vals:
        if isinstance(v, (SymArray, _TracedRef)) or is_symbolic(v):
            return True
    return False


def _shape_of(x):
    return tuple(x.shape)


def _make_patches():
    """(module, attr, wrapper-factory) table; built lazily so importing the
    frontend never drags jax in."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    patches = []

    def patch(mod, attrname, make):
        orig = getattr(mod, attrname)
        patches.append((mod, attrname, orig, make(orig)))

    # ---- pallas_call ---------------------------------------------------
    def mk_pallas_call(orig):
        def pallas_call(kernel, *a, out_shape=None, grid=None, in_specs=None,
                        out_specs=None, scratch_shapes=(), **kw):
            if _CTX is None:
                if out_shape is None and a:
                    return orig(kernel, *a, grid=grid, in_specs=in_specs,
                                out_specs=out_specs,
                                scratch_shapes=scratch_shapes, **kw)
                return orig(kernel, *a, out_shape=out_shape, grid=grid,
                            in_specs=in_specs, out_specs=out_specs,
                            scratch_shapes=scratch_shapes, **kw)
            ctx = _CTX
            if out_shape is None and a:
                out_shape, a = a[0], a[1:]
            ctx.capture(kernel, grid, in_specs, out_specs, out_shape,
                        scratch_shapes)

            def recorded(*call_args):
                ctx.captured["call_args"] = call_args
                if isinstance(out_shape, (list, tuple)):
                    return type(out_shape)(
                        _TracedOutput(ctx.name, o.shape, o.dtype)
                        for o in out_shape)
                return _TracedOutput(ctx.name, out_shape.shape,
                                     out_shape.dtype)

            return recorded

        return pallas_call

    patch(pl, "pallas_call", mk_pallas_call)

    # ---- body primitives ----------------------------------------------
    def mk_program_id(orig):
        def program_id(axis):
            if _CTX is None or not _CTX.body_active:
                return orig(axis)
            return affine(grid_sym(axis))

        return program_id

    patch(pl, "program_id", mk_program_id)

    def mk_when(orig):
        def when(condition):
            if _CTX is None or not _CTX.body_active:
                return orig(condition)

            # trace both sides of the branch: execute the guarded body
            # unconditionally (the estimator prices per-step structure)
            def run(fn):
                fn()
                return fn

            return run

        return when

    patch(pl, "when", mk_when)

    # ---- jnp / lax surface ---------------------------------------------
    def mk_minmax(orig, clamp_attr):
        def minmax(a, b):
            if not _sym_args(a, b):
                return orig(a, b)
            if isinstance(a, SymArray) or isinstance(b, SymArray):
                arr = a if isinstance(a, SymArray) else b
                return arr._binop(b if arr is a else a)
            # index-map clamp: one side must be a concrete integer
            ea, eb = a, b
            if isinstance(eb, AffineExpr) and not isinstance(ea, AffineExpr):
                ea, eb = eb, ea
            if isinstance(eb, AffineExpr):
                if not eb.is_const:
                    raise NonAffineError(
                        f"{clamp_attr}({ea!r}, {eb!r}) of two symbolic "
                        f"expressions is not affine")
                eb = eb.const
            return (affine(ea).clamp_lo(int(eb)) if clamp_attr == "maximum"
                    else affine(ea).clamp_hi(int(eb)))

        return minmax

    patch(jnp, "maximum", lambda orig: mk_minmax(orig, "maximum"))
    patch(jnp, "minimum", lambda orig: mk_minmax(orig, "minimum"))

    def mk_dot(orig):
        def dot(a, b, **kw):
            if not _sym_args(a, b):
                return orig(a, b, **kw)
            return _record_matmul(_CTX, a, b)

        return dot

    patch(jnp, "dot", mk_dot)

    def mk_dot_general(orig):
        def dot_general(a, b, dimension_numbers, **kw):
            if not _sym_args(a, b):
                return orig(a, b, dimension_numbers, **kw)
            ctx = _CTX
            (lc, rc), (lb, rb) = dimension_numbers
            if lb or rb or a.ndim != 2 or b.ndim != 2 \
                    or len(lc) != 1 or len(rc) != 1:
                raise TraceError(ctx.name, "dot_general",
                                 f"unsupported dimension numbers "
                                 f"{dimension_numbers} for shapes "
                                 f"{a.shape}, {b.shape}")
            lhs = ctx._consume(a)
            rhs = ctx._consume(b)
            m = a.shape[1 - lc[0]]
            k = a.shape[lc[0]]
            n = b.shape[1 - rc[0]]
            ctx.body.matmuls.append(BodyMatmul(m, k, n, lhs, rhs))
            return SymArray((m, n), np.float32, None, ctx)

        return dot_general

    patch(jax.lax, "dot_general", mk_dot_general)

    def mk_unary(orig):
        def unary(x, *a, **kw):
            if not isinstance(x, SymArray):
                return orig(x, *a, **kw)
            return x._binop(0.0)

        return unary

    for mod, names in ((jnp, ("exp", "abs", "sqrt", "tanh")),
                       (jax.lax, ("rsqrt", "exp"))):
        for fname in names:
            patch(mod, fname, mk_unary)

    def mk_where(orig):
        def where(c, a=None, b=None):
            if not _sym_args(c, a, b):
                return orig(c, a, b)
            arrs = [x for x in (c, a, b) if isinstance(x, SymArray)]
            if not arrs:
                # scalar select on a symbolic predicate — a scalar unknown
                return SymArray((), np.float32, None, _CTX)
            out = arrs[0]._binop(arrs[1] if len(arrs) > 1 else 0.0)
            for extra in arrs[2:]:
                out.ctx._consume(extra)
            return out

        return where

    patch(jnp, "where", mk_where)

    def mk_like(orig):
        def like(x, *a, **kw):
            if not isinstance(x, (SymArray, _TracedRef)):
                return orig(x, *a, **kw)
            ctx = x.ctx
            return SymArray(x.shape, x.dtype, None, ctx)

        return like

    patch(jnp, "zeros_like", mk_like)
    patch(jnp, "ones_like", mk_like)
    patch(jnp, "full_like", mk_like)

    def mk_stack(orig):
        def stack(arrays, axis=0, **kw):
            arrays = list(arrays)
            if not any(isinstance(x, SymArray) for x in arrays):
                return orig(arrays, axis=axis, **kw)
            ctx = next(x.ctx for x in arrays if isinstance(x, SymArray))
            for x in arrays:
                if isinstance(x, SymArray):
                    ctx._consume(x)
            base = _shape_of(arrays[0])
            axis = axis % (len(base) + 1)
            shape = base[:axis] + (len(arrays),) + base[axis:]
            return SymArray(shape, arrays[0].dtype, None, ctx)

        return stack

    patch(jnp, "stack", mk_stack)

    def mk_concatenate(orig):
        def concatenate(arrays, axis=0, **kw):
            arrays = list(arrays)
            if not any(isinstance(x, SymArray) for x in arrays):
                return orig(arrays, axis=axis, **kw)
            ctx = next(x.ctx for x in arrays if isinstance(x, SymArray))
            for x in arrays:
                if isinstance(x, SymArray):
                    ctx._consume(x)
            base = list(_shape_of(arrays[0]))
            axis = axis % len(base)
            base[axis] = sum(_shape_of(x)[axis] for x in arrays)
            return SymArray(tuple(base), arrays[0].dtype, None, ctx)

        return concatenate

    patch(jnp, "concatenate", mk_concatenate)

    def mk_transpose(orig):
        def transpose(x, axes=None):
            if not isinstance(x, SymArray):
                return orig(x, axes)
            return _transpose(x, axes)

        return transpose

    patch(jnp, "transpose", mk_transpose)

    def mk_iota(orig):
        def broadcasted_iota(dtype, shape, dimension):
            if _CTX is None or not _CTX.body_active:
                return orig(dtype, shape, dimension)
            return SymArray(shape, dtype, None, _CTX)

        return broadcasted_iota

    patch(jax.lax, "broadcasted_iota", mk_iota)

    return patches


class _patched:
    """Context manager installing/removing the tracing patch table."""

    def __init__(self, ctx: _Trace):
        self.ctx = ctx
        self.patches = []

    def __enter__(self):
        global _CTX
        if _CTX is not None:
            raise TraceError(self.ctx.name, "trace",
                             "nested kernel traces are not supported")
        self.patches = _make_patches()
        for mod, attrname, _orig, wrapper in self.patches:
            setattr(mod, attrname, wrapper)
        _CTX = self.ctx
        return self.ctx

    def __exit__(self, *exc):
        global _CTX
        _CTX = None
        for mod, attrname, orig, _wrapper in reversed(self.patches):
            setattr(mod, attrname, orig)
        return False


# --------------------------------------------------------------------------
# capture post-processing
# --------------------------------------------------------------------------
def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _scratch_info(name, scratch_shapes) -> tuple:
    out = []
    for s in _as_list(scratch_shapes):
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is None or dtype is None:
            raise TraceError(name, "scratch",
                             f"unsupported scratch entry {s!r} (need "
                             f".shape/.dtype, e.g. pltpu.VMEM)")
        out.append(TracedScratch(tuple(shape),
                                 int(np.dtype(dtype).itemsize)))
    return tuple(out)


def _eval_index_map(name, opname, spec, grid):
    block_shape = tuple(spec.block_shape)
    if any(b is None for b in block_shape):
        raise TraceError(name, f"operand {opname!r}",
                         "BlockSpec with None (unblocked) dims is not "
                         "supported by the tracer")
    index_map = spec.index_map
    if index_map is None:
        raise TraceError(name, f"operand {opname!r}",
                         "BlockSpec without an index_map")
    syms = [affine(grid_sym(d)) for d in range(len(grid))]
    try:
        idx = index_map(*syms)
    except (NonAffineError, TypeError, ValueError) as e:
        raise TraceError(name, f"operand {opname!r}",
                         f"non-affine index map: {e}") from e
    if not isinstance(idx, tuple):
        idx = (idx,)
    if len(idx) != len(block_shape):
        raise TraceError(name, f"operand {opname!r}",
                         f"index map arity {len(idx)} != block rank "
                         f"{len(block_shape)}")
    exprs = []
    for coord in idx:
        if isinstance(coord, (SymArray, _TracedRef)):
            raise TraceError(name, f"operand {opname!r}",
                             "index map returned a traced array value "
                             "(data-dependent block index)")
        try:
            exprs.append(affine(coord))
        except NonAffineError as e:
            raise TraceError(name, f"operand {opname!r}",
                             f"non-affine index map coordinate: {e}") from e
    deps = set()
    for e in exprs:
        deps |= {int(s.name[1:]) for s in e.free_syms()}
    return block_shape, tuple(exprs), tuple(sorted(deps))


def _validate_grid(name, grid):
    if grid is None:
        raise TraceError(name, "grid", "pallas_call without a grid")
    if not isinstance(grid, tuple):
        grid = (grid,)
    out = []
    for g in grid:
        if isinstance(g, (SymArray, _TracedRef, AffineExpr, Sym)) or \
                not isinstance(g, (int, np.integer)) or isinstance(g, bool):
            raise TraceError(
                name, "grid",
                f"data-dependent grid entry {g!r} — the estimator needs a "
                f"static launch structure (hoist the size to a Python int)")
        out.append(int(g))
    return tuple(out)


def trace_kernel(call_fn, args, *, name: str = "kernel",
                 operand_names=None, out_names=None,
                 trace_body: bool = False,
                 require_body: bool = False) -> TracedKernel:
    """Trace one Pallas kernel builder into a :class:`TracedKernel`.

    ``call_fn`` is the builder's calling convention (e.g. the closure
    returned by ``make_matmul(...)``); ``args`` its positional arguments as
    :func:`arg` placeholders.  ``operand_names`` optionally names every
    pallas operand (inputs then outputs) — by default names derive from the
    argument each operand binds to.  With ``trace_body=True`` the kernel
    body is additionally executed over symbolic refs; body failures are
    recorded (``traced.body.error``) unless ``require_body=True``.
    """
    args = tuple(args)
    ctx = _Trace(name, args)
    with _patched(ctx):
        try:
            call_fn(*args)
        except TraceError:
            raise
        except NonAffineError as e:
            raise TraceError(name, "builder", str(e)) from e
        cap = ctx.captured
        if cap is None:
            raise TraceError(name, "builder",
                             "builder never invoked pl.pallas_call")
        traced = _postprocess(ctx, cap, name, operand_names, out_names)
        if trace_body:
            _run_body(ctx, cap, traced, require_body)
    return traced


def _postprocess(ctx: _Trace, cap: dict, name: str, operand_names,
                 out_names) -> TracedKernel:
    """Evaluate index maps and assemble the TracedKernel (runs inside the
    patch context: index maps may call patched jnp functions)."""
    args = ctx.args
    grid = _validate_grid(name, cap["grid"])
    call_args = cap.get("call_args", ())
    in_specs = _as_list(cap["in_specs"])
    out_specs = _as_list(cap["out_specs"])
    out_shapes = _as_list(cap["out_shape"])
    if len(call_args) != len(in_specs):
        raise TraceError(name, "pallas_call",
                         f"{len(call_args)} call arguments vs "
                         f"{len(in_specs)} in_specs")
    if len(out_specs) != len(out_shapes):
        raise TraceError(name, "pallas_call",
                         f"{len(out_specs)} out_specs vs "
                         f"{len(out_shapes)} out_shapes")

    # match every pallas operand to the builder argument it binds
    arg_pos = {id(a): i for i, a in enumerate(args)}
    uses = {}
    bindings = []
    for ca in call_args:
        pos = arg_pos.get(id(ca))
        if pos is None:
            raise TraceError(
                name, "pallas_call",
                "an operand is not one of the traced placeholder arguments "
                "(builders must pass their inputs through unchanged)")
        uses[pos] = uses.get(pos, 0) + 1
        bindings.append((pos, uses[pos] - 1))
    default_names = []
    for pos, ordinal in bindings:
        base = args[pos].name
        default_names.append(base if uses[pos] == 1 else f"{base}{ordinal}")
    for i, _shape in enumerate(out_shapes):
        default_names.append(
            (_as_list(out_names)[i] if out_names is not None
             else ("out" if len(out_shapes) == 1 else f"out{i}")))
    names = list(operand_names) if operand_names is not None else default_names
    n_ops = len(in_specs) + len(out_specs)
    if len(names) != n_ops:
        raise TraceError(name, "operand_names",
                         f"{len(names)} names for {n_ops} operands")

    operands = []
    for i, (spec, (pos, _ord)) in enumerate(zip(in_specs, bindings)):
        block_shape, exprs, deps = _eval_index_map(name, names[i], spec, grid)
        ph = args[pos]
        operands.append(TracedOperand(
            name=names[i], block_shape=block_shape,
            elem_bytes=int(np.dtype(ph.dtype).itemsize),
            index_exprs=exprs, grid_deps=deps, is_output=False,
            arg_name=ph.name, arg_shape=tuple(ph.shape), arg_pos=pos))
    for j, (spec, oshape) in enumerate(zip(out_specs, out_shapes)):
        opname = names[len(in_specs) + j]
        block_shape, exprs, deps = _eval_index_map(name, opname, spec, grid)
        operands.append(TracedOperand(
            name=opname, block_shape=block_shape,
            elem_bytes=int(np.dtype(oshape.dtype).itemsize),
            index_exprs=exprs, grid_deps=deps, is_output=True,
            arg_name=opname, arg_shape=tuple(oshape.shape),
            arg_pos=len(args) + j))

    scratch = _scratch_info(name, cap["scratch_shapes"])
    return TracedKernel(name=name, grid=grid, operands=tuple(operands),
                        scratch=scratch, body=ctx.body)


def _ref_dtype(elem_bytes: int):
    return np.dtype(f"f{elem_bytes}") if elem_bytes in (2, 4, 8) else np.uint8


def _run_body(ctx: _Trace, cap: dict, traced: TracedKernel,
              require_body: bool) -> None:
    """Execute the kernel body over symbolic refs (inside the patch
    context trace_kernel already holds)."""
    refs = [
        _TracedRef(ctx, "op", i, op.name, op.block_shape,
                   _ref_dtype(op.elem_bytes))
        for i, op in enumerate(traced.operands)
    ]
    scr = [
        _TracedRef(ctx, "scratch", i, f"scratch{i}", s.shape,
                   _ref_dtype(s.elem_bytes))
        for i, s in enumerate(traced.scratch)
    ]
    ctx.body_active = True
    try:
        cap["kernel"](*refs, *scr)
        ctx.body.ok = True
    except TraceError as e:
        if require_body:
            raise
        ctx.body.error = str(e)
    except NonAffineError as e:
        err = TraceError(ctx.name, "kernel body", str(e))
        if require_body:
            raise err from e
        ctx.body.error = str(err)
    finally:
        ctx.body_active = False
