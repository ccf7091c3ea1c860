"""hornshape verifier: BlockSpec/grid safety proofs with counterexamples.

Given a captured ``pallas_call`` geometry (``symbolic.Capture`` or a
directly-constructed :class:`Geometry`), prove per grid launch:

* **HS001 in-bounds** — every block-index an ``index_map`` can produce is
  inside ``[0, ceil(dim / block) - 1]`` for *every* grid step (including
  ragged tails), and every scalar-table lookup index is inside the table.
* **HS002 coverage hole / HS003 double-write** — the output grid, reduced
  over legitimate accumulator-carry dims (grid dims the out map is
  independent of *and* that are declared ``"arbitrary"``), covers each
  output block exactly once.  A revisit dim declared ``"parallel"`` is a
  double-write by construction.  An output aliased to an input
  (``input_output_aliases``) is an in-place update and is not held to
  coverage: the blocks no step writes keep the input's values.
* **HS004 consistency** — index-map arity vs array rank, block-shape rank,
  ``input_output_aliases`` dtype/shape agreement, positive scratch shapes.
* **HS005 null-page contract** — block-table gathers must select the
  module's ``NULL_PAGE`` for dead steps and clamp with ``min(_, W - 1)``
  where ``W`` is the table width (the pool's page-table width), checked
  symbolically, not syntactically (HL304's upgrade).
* **HS006 analysis incomplete** — the geometry defeats both the symbolic
  domains and bounded enumeration; reported, never silently passed.

Verdicts are decided symbolically where the interval/congruence domains
suffice (``method == "symbolic"``), else by exact enumeration of every
grid point (``method == "enumerated"``) — so a clean report is a proof
either way, and every failure carries a concrete counterexample grid
point.  ``brute_force`` recomputes all verdicts purely by enumeration;
the hypothesis property test checks the two always agree.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.core import Finding
from repro.analysis.symbolic import (AnalysisError, Capture, GridSpecV,
                                     ScratchV, ShapeDtypeV, Sym, SymBool,
                                     Table, concrete_all, free_vars,
                                     lookups_in, prove, sym, var)

_ENUM_LIMIT = 200_000

RULES = {
    "HS001": "index_map window out of bounds for some grid step",
    "HS002": "output grid leaves a block unwritten (coverage hole)",
    "HS003": "output block written more than once outside an "
             "accumulator-carry dim (double-write)",
    "HS004": "BlockSpec/alias/scratch inconsistency (rank, dtype, shape)",
    "HS005": "block-table gather violates the null-page clamp contract",
    "HS006": "geometry defeats symbolic + enumeration analysis",
}


class GeometryError(Exception):
    """The capture cannot be turned into a checkable geometry."""


@dataclass
class Operand:
    name: str
    shape: Tuple[int, ...]
    dtype: str
    block_shape: Tuple[int, ...]
    index_map: object                    # callable on (grid syms[, tables])
    memory_space: Optional[str] = None

    def nblocks(self) -> Tuple[int, ...]:
        return tuple(-(-s // b) for s, b in zip(self.shape, self.block_shape))


@dataclass
class Geometry:
    name: str
    grid: Tuple[int, ...]
    in_operands: List[Operand]
    out_operands: List[Operand]
    scalar_tables: List[Table] = field(default_factory=list)
    scratch: List[ScratchV] = field(default_factory=list)
    dimension_semantics: Optional[Tuple[str, ...]] = None
    input_output_aliases: Optional[Dict[int, int]] = None
    # (block-table name, NULL_PAGE): every gather into that table must be
    # where-guarded to NULL_PAGE and min-clamped to the table width - 1
    null_page: Optional[Tuple[str, int]] = None
    path: str = "<geometry>"
    lineno: int = 0

    def grid_env(self) -> Dict[str, Tuple[int, int]]:
        return {f"g{d}": (0, e - 1) for d, e in enumerate(self.grid)}

    def grid_vars(self) -> Tuple[Sym, ...]:
        return tuple(var(f"g{d}") for d in range(len(self.grid)))


@dataclass
class Report:
    geometry: Geometry
    findings: List[Finding] = field(default_factory=list)
    verdicts: Dict[tuple, object] = field(default_factory=dict)
    methods: Dict[tuple, str] = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def proved_symbolically(self) -> int:
        return sum(1 for m in self.methods.values() if m == "symbolic")

    def render(self) -> List[str]:
        out = [f"{self.geometry.name}: grid={self.geometry.grid} "
               f"in={len(self.geometry.in_operands)} "
               f"out={len(self.geometry.out_operands)}"]
        if self.ok:
            n_sym = self.proved_symbolically()
            n_enum = sum(1 for m in self.methods.values()
                         if m == "enumerated")
            out.append(f"  PROVED: {len(self.verdicts)} obligations "
                       f"({n_sym} symbolic, {n_enum} enumerated)")
        for f in self.findings:
            out.append("  " + f.render())
        return out


# --------------------------------------------------------------------------
# capture -> geometry
# --------------------------------------------------------------------------
def _as_list(x) -> list:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def geometry_from_capture(cap: Capture, name: str,
                          path: str = "<capture>",
                          null_page: Optional[Tuple[str, int]] = None
                          ) -> Geometry:
    kw = cap.kwargs
    gs = kw.get("grid_spec")
    if isinstance(gs, GridSpecV):
        nsp = int(gs.num_scalar_prefetch or 0)
        grid, in_specs = gs.grid, _as_list(gs.in_specs)
        out_specs, scratch = _as_list(gs.out_specs), _as_list(
            gs.scratch_shapes)
    else:
        nsp = 0
        grid = tuple(kw.get("grid") or ())
        in_specs = _as_list(kw.get("in_specs"))
        out_specs = _as_list(kw.get("out_specs"))
        scratch = _as_list(kw.get("scratch_shapes"))
    if not grid or not all(isinstance(e, int) and e > 0 for e in grid):
        raise GeometryError(f"{name}: grid {grid!r} is not a tuple of "
                            f"positive ints")
    tables = list(cap.args[:nsp])
    for t in tables:
        if not isinstance(t, Table):
            raise GeometryError(
                f"{name}: scalar-prefetch operand {t!r} is not a Table")
    data_args = cap.args[nsp:]
    if len(in_specs) != len(data_args):
        raise GeometryError(
            f"{name}: {len(in_specs)} in_specs but {len(data_args)} "
            f"non-scalar call args")
    ins = []
    for i, (spec, arr) in enumerate(zip(in_specs, data_args)):
        ins.append(Operand(
            name=f"in{i}", shape=tuple(arr.shape),
            dtype=getattr(arr, "dtype", "int32"),
            block_shape=spec.block_shape, index_map=spec.index_map,
            memory_space=getattr(spec, "memory_space", None)))
    out_shapes = _as_list(kw.get("out_shape"))
    if len(out_specs) != len(out_shapes):
        raise GeometryError(
            f"{name}: {len(out_specs)} out_specs but {len(out_shapes)} "
            f"out_shapes")
    outs = []
    for i, (spec, sds) in enumerate(zip(out_specs, out_shapes)):
        if not isinstance(sds, ShapeDtypeV):
            raise GeometryError(f"{name}: out_shape {sds!r} is not a "
                                f"ShapeDtypeStruct")
        outs.append(Operand(
            name=f"out{i}", shape=sds.shape, dtype=sds.dtype,
            block_shape=spec.block_shape, index_map=spec.index_map,
            memory_space=getattr(spec, "memory_space", None)))
    cp = kw.get("compiler_params")
    sem = None
    if isinstance(cp, dict) and cp.get("dimension_semantics") is not None:
        sem = tuple(cp["dimension_semantics"])
    aliases = kw.get("input_output_aliases")
    # JAX numbers aliased inputs over every call operand, scalar-prefetch
    # operands first; the geometry numbers the BlockSpec'd operands only
    aliases = {i - nsp if isinstance(i, int) else i: o
               for i, o in dict(aliases).items()} if aliases else None
    return Geometry(name=name, grid=tuple(grid), in_operands=ins,
                    out_operands=outs, scalar_tables=tables,
                    scratch=[s for s in scratch if isinstance(s, ScratchV)],
                    dimension_semantics=sem, input_output_aliases=aliases,
                    null_page=null_page, path=path, lineno=cap.lineno)


# --------------------------------------------------------------------------
# shared evaluation helpers
# --------------------------------------------------------------------------
def _call_index_map(geom: Geometry, op: Operand, args):
    im = op.index_map
    if im is None:
        # pallas default: identity over leading grid dims
        return tuple(args[:len(op.shape)])
    if geom.scalar_tables:
        return im(*args, *geom.scalar_tables)
    return im(*args)


def _idx_tuple(geom: Geometry, op: Operand):
    """Symbolic index tuple of ``op``'s map, or an HS004/HS006 message."""
    try:
        res = _call_index_map(geom, op, geom.grid_vars())
    except AnalysisError as e:
        raise GeometryError(f"{op.name} index_map: {e}")
    if isinstance(res, (Sym, int)):
        res = (res,)
    if not isinstance(res, tuple):
        raise GeometryError(
            f"{op.name} index_map returned {type(res).__name__}, "
            f"expected a tuple of block indices")
    return tuple(sym(x) for x in res)


def _iter_grid(grid: Sequence[int], dims: Optional[Sequence[int]] = None):
    dims = list(range(len(grid))) if dims is None else list(dims)
    point = [0] * len(grid)

    def rec(i):
        if i == len(dims):
            yield {f"g{d}": point[d] for d in range(len(grid))}
            return
        d = dims[i]
        for v in range(grid[d]):
            point[d] = v
            yield from rec(i + 1)

    yield from rec(0)


def _fmt_point(point: Dict[str, int], dims: Optional[Sequence[int]] = None):
    keys = sorted(point, key=lambda k: int(k[1:]))
    if dims is not None:
        keep = {f"g{d}" for d in dims}
        keys = [k for k in keys if k in keep]
    return "(" + ", ".join(f"{k}={point[k]}" for k in keys) + ")"


def _enum_size(grid: Sequence[int], dims=None) -> int:
    dims = range(len(grid)) if dims is None else dims
    return math.prod(grid[d] for d in dims) if dims else 1


# --------------------------------------------------------------------------
# the verifier
# --------------------------------------------------------------------------
class _Verifier:
    def __init__(self, geom: Geometry):
        self.g = geom
        self.rep = Report(geom)
        self.env = geom.grid_env()

    def finding(self, rule: str, message: str):
        self.rep.findings.append(Finding(
            rule, self.g.path, self.g.lineno, 0,
            f"{self.g.name}: {message}", self.g.name))

    # -- obligations ---------------------------------------------------
    def _discharge(self, key, ob: SymBool, describe, value_expr=None):
        """Prove ``ob`` for all grid points or find a counterexample."""
        v = prove(ob, self.env)
        if v is True:
            self.rep.verdicts[key] = True
            self.rep.methods[key] = "symbolic"
            return
        if _enum_size(self.g.grid) > _ENUM_LIMIT:
            self.rep.verdicts[key] = None
            self.rep.methods[key] = "incomplete"
            self.finding("HS006", f"{describe}: inconclusive symbolically "
                                  f"and grid too large to enumerate")
            return
        for point in _iter_grid(self.g.grid):
            try:
                vals = concrete_all(ob, point)
            except AnalysisError as e:
                self.rep.verdicts[key] = None
                self.rep.methods[key] = "incomplete"
                self.finding("HS006", f"{describe}: {e}")
                return
            if False in vals:
                self.rep.verdicts[key] = False
                self.rep.methods[key] = "enumerated"
                detail = ""
                if value_expr is not None:
                    got = sorted(concrete_all(value_expr, point))
                    detail = f" (index value {got[0] if len(got) == 1 else got})"
                self.finding("HS001", f"{describe}: counterexample grid "
                                      f"point {_fmt_point(point)}{detail}")
                return
        self.rep.verdicts[key] = True
        self.rep.methods[key] = "enumerated"

    def check_operand(self, op: Operand):
        nd = len(op.shape)
        if op.block_shape is None or len(op.block_shape) != nd:
            self.finding("HS004", f"{op.name}: block_shape "
                                  f"{op.block_shape} does not match array "
                                  f"rank {nd} (shape {op.shape})")
            return
        try:
            idx = _idx_tuple(self.g, op)
        except GeometryError as e:
            self.finding("HS006", str(e))
            return None
        if len(idx) != nd:
            self.finding("HS004", f"{op.name}: index_map returns "
                                  f"{len(idx)} indices but the array has "
                                  f"rank {nd}")
            return None
        for d, e in enumerate(idx):
            hi = op.nblocks()[d] - 1
            self._discharge(
                ("inbounds", op.name, d),
                (e >= 0) & (e <= hi),
                f"{op.name} dim {d}: block index {e!r} must be in "
                f"[0, {hi}]", value_expr=e)
        # scalar-table lookup indices must themselves be in bounds
        for li, lk in enumerate(self._lookups(idx)):
            table = lk.args[0]
            for k, ie in enumerate(lk.args[1]):
                bound = table.shape[k] - 1
                self._discharge(
                    ("lookup", op.name, li, k),
                    (ie >= 0) & (ie <= bound),
                    f"{op.name}: lookup index {k} into {table.name} "
                    f"{ie!r} must be in [0, {bound}]", value_expr=ie)
        return idx

    @staticmethod
    def _lookups(idx) -> List[Sym]:
        seen, out = set(), []
        for e in idx:
            for lk in lookups_in(e):
                if id(lk) not in seen:
                    seen.add(id(lk))
                    out.append(lk)
        return out

    # -- coverage ------------------------------------------------------
    def check_coverage(self, op: Operand, idx):
        key = ("coverage", op.name)
        fv = set()
        for e in idx:
            fv |= free_vars(e)
        revisit = [d for d in range(len(self.g.grid))
                   if f"g{d}" not in fv and self.g.grid[d] > 1]
        sem = self.g.dimension_semantics
        for d in revisit:
            if sem is not None and d < len(sem) and sem[d] == "parallel":
                self.rep.verdicts[key] = "double"
                self.rep.methods[key] = "symbolic"
                self.finding(
                    "HS003", f"{op.name}: grid dim {d} (extent "
                    f"{self.g.grid[d]}) revisits every output block but is "
                    f"declared 'parallel' — double-write across cores")
                return
        reduced = [d for d in range(len(self.g.grid)) if d not in revisit]
        if self._bijection_fast_path(op, idx, reduced):
            self.rep.verdicts[key] = "exact"
            self.rep.methods[key] = "symbolic"
            return
        self._coverage_enumerate(op, idx, reduced, key)

    def _bijection_fast_path(self, op: Operand, idx, reduced) -> bool:
        """Each out dim is either the constant 0 (single block) or a
        distinct reduced grid var with coefficient 1 and matching extent;
        all reduced vars consumed -> a bijection, proved symbolically."""
        from repro.analysis.symbolic import _linearize
        used = set()
        nb = op.nblocks()
        for d, e in enumerate(idx):
            try:
                c, vs, ops = _linearize(e)
            except AnalysisError:
                return False
            if ops:
                return False
            if not vs:
                if c == 0 and nb[d] == 1:
                    continue
                return False
            if len(vs) != 1 or c != 0:
                return False
            (name, coeff), = vs.items()
            if coeff != 1 or name in used or not name.startswith("g"):
                return False
            gd = int(name[1:])
            if gd not in reduced or self.g.grid[gd] != nb[d]:
                return False
            used.add(name)
        return used == {f"g{d}" for d in reduced if self.g.grid[d] > 1} \
            or used == {f"g{d}" for d in reduced}

    def _coverage_enumerate(self, op: Operand, idx, reduced, key):
        nb = op.nblocks()
        if _enum_size(self.g.grid, reduced) > _ENUM_LIMIT \
                or math.prod(nb) > _ENUM_LIMIT:
            self.rep.verdicts[key] = None
            self.rep.methods[key] = "incomplete"
            self.finding("HS006", f"{op.name}: coverage not provable "
                                  f"symbolically and grid too large to "
                                  f"enumerate")
            return
        counts: Dict[tuple, dict] = {}
        for point in _iter_grid(self.g.grid, reduced):
            vals = []
            for e in idx:
                try:
                    vs = concrete_all(e, point)
                except AnalysisError as err:
                    self.rep.verdicts[key] = None
                    self.rep.methods[key] = "incomplete"
                    self.finding("HS006", f"{op.name}: coverage: {err}")
                    return
                if len(vs) != 1:
                    self.rep.verdicts[key] = None
                    self.rep.methods[key] = "incomplete"
                    self.finding(
                        "HS006", f"{op.name}: output index depends on "
                        f"scalar-table contents at {_fmt_point(point, reduced)}"
                        f" — cannot prove exact coverage")
                    return
                vals.append(next(iter(vs)))
            block = tuple(vals)
            entry = counts.setdefault(block, {"n": 0, "first": None})
            if entry["first"] is None:
                entry["first"] = _fmt_point(point, reduced)
            elif entry["n"] == 1:
                self.rep.verdicts[key] = "double"
                self.rep.methods[key] = "enumerated"
                self.finding(
                    "HS003", f"{op.name}: output block {block} written by "
                    f"both grid points {entry['first']} and "
                    f"{_fmt_point(point, reduced)}")
                return
            entry["n"] += 1
        for block_idx in _iter_grid(nb):
            block = tuple(block_idx[f"g{d}"] for d in range(len(nb)))
            if block not in counts:
                self.rep.verdicts[key] = "hole"
                self.rep.methods[key] = "enumerated"
                self.finding(
                    "HS002", f"{op.name}: output block {block} is never "
                    f"written (coverage hole over blocks {nb})")
                return
        self.rep.verdicts[key] = "exact"
        self.rep.methods[key] = "enumerated"

    # -- aliases / scratch / null page ---------------------------------
    def check_aliases(self):
        al = self.g.input_output_aliases
        if not al:
            return
        for i, o in al.items():
            if not (isinstance(i, int) and 0 <= i < len(self.g.in_operands)
                    and isinstance(o, int)
                    and 0 <= o < len(self.g.out_operands)):
                self.finding("HS004", f"input_output_aliases {{{i}: {o}}} "
                                      f"out of operand range")
                continue
            a, b = self.g.in_operands[i], self.g.out_operands[o]
            if a.shape != b.shape or a.dtype != b.dtype:
                self.finding(
                    "HS004", f"alias in{i}->out{o}: {a.shape}/{a.dtype} vs "
                    f"{b.shape}/{b.dtype} — donated buffers must match "
                    f"exactly")
            elif a.block_shape != b.block_shape:
                self.finding(
                    "HS004", f"alias in{i}->out{o}: block shapes "
                    f"{a.block_shape} vs {b.block_shape} differ")

    def check_scratch(self):
        for i, s in enumerate(self.g.scratch):
            if not all(isinstance(d, int) and d > 0 for d in s.shape):
                self.finding("HS004", f"scratch {i}: shape {s.shape} must "
                                      f"be positive ints")

    def check_null_page(self):
        if self.g.null_page is None:
            return
        table_name, null_page = self.g.null_page
        tables = {t.name: t for t in self.g.scalar_tables}
        if table_name not in tables:
            self.finding("HS005", f"null-page contract names table "
                                  f"{table_name!r} but the geometry has "
                                  f"{sorted(tables)}")
            return
        width = tables[table_name].shape[-1]
        key = ("null_page",)
        checked = 0
        for op in self.g.in_operands:
            try:
                idx = _idx_tuple(self.g, op)
            except GeometryError:
                continue
            for lk in self._lookups(idx):
                if lk.args[0].name != table_name:
                    continue
                checked += 1
                if not self._null_guarded(idx, lk, null_page):
                    self.finding(
                        "HS005", f"{op.name}: gather {lk!r} has no "
                        f"where(live, ..., {null_page}) guard selecting "
                        f"NULL_PAGE={null_page} for dead grid steps")
                    self.rep.verdicts[key] = False
                    self.rep.methods[key] = "symbolic"
                    return
                clamp = self._min_clamp_const(lk)
                if clamp is None:
                    self.finding(
                        "HS005", f"{op.name}: gather {lk!r} index has no "
                        f"min(_, const) clamp into the table")
                    self.rep.verdicts[key] = False
                    self.rep.methods[key] = "symbolic"
                    return
                if prove(sym(clamp) == width - 1, {}) is not True:
                    self.finding(
                        "HS005", f"{op.name}: clamp bound {clamp} != table "
                        f"width - 1 = {width - 1} — the clamp must equal "
                        f"the block-table width")
                    self.rep.verdicts[key] = False
                    self.rep.methods[key] = "symbolic"
                    return
        if checked:
            self.rep.verdicts[key] = True
            self.rep.methods[key] = "symbolic"

    @staticmethod
    def _null_guarded(idx, lk: Sym, null_page: int) -> bool:
        def holds(e) -> bool:
            stack = [e]
            while stack:
                n = stack.pop()
                if n is lk:
                    return True
                if isinstance(n, Sym):
                    if n.op == "lookup":
                        stack.extend(n.args[1])
                    else:
                        stack.extend(n.args)
                elif isinstance(n, SymBool):
                    stack.extend(a for a in n.args
                                 if isinstance(a, (Sym, SymBool)))
            return False

        for e in idx:
            stack = [e]
            while stack:
                n = stack.pop()
                if isinstance(n, Sym):
                    if n.op == "where":
                        _, a, b = n.args
                        if holds(a) and b.op == "const" \
                                and b.args[0] == null_page:
                            return True
                    if n.op == "lookup":
                        stack.extend(n.args[1])
                    else:
                        stack.extend(n.args)
                elif isinstance(n, SymBool):
                    stack.extend(x for x in n.args
                                 if isinstance(x, (Sym, SymBool)))
        return False

    @staticmethod
    def _min_clamp_const(lk: Sym) -> Optional[int]:
        for ie in lk.args[1]:
            stack = [ie]
            while stack:
                n = stack.pop()
                if isinstance(n, Sym):
                    if n.op == "min":
                        for a in n.args:
                            if a.op == "const":
                                return a.args[0]
                    stack.extend(a for a in n.args if isinstance(a, Sym))
        return None


def verify(geom: Geometry) -> Report:
    v = _Verifier(geom)
    for op in geom.in_operands:
        v.check_operand(op)
    aliased = {f"out{o}" for o in (geom.input_output_aliases or {}).values()}
    for op in geom.out_operands:
        idx = v.check_operand(op)
        # an output aliased to an input is updated in place: blocks no
        # grid step writes keep the input's values, so it has no holes
        # (and may be written at rows a scalar table chooses)
        if idx is not None and op.name not in aliased:
            v.check_coverage(op, idx)
    v.check_aliases()
    v.check_scratch()
    v.check_null_page()
    return v.rep


# --------------------------------------------------------------------------
# ground truth: exhaustive enumeration (the property test's oracle)
# --------------------------------------------------------------------------
def brute_force(geom: Geometry) -> Dict[tuple, object]:
    """Recompute every in-bounds/lookup/coverage verdict by enumerating
    all grid points.  Raises GeometryError if the geometry is too large
    or genuinely not enumerable."""
    if _enum_size(geom.grid) > _ENUM_LIMIT:
        raise GeometryError("grid too large to brute-force")
    verdicts: Dict[tuple, object] = {}
    idx_of = {}
    for op in geom.in_operands + geom.out_operands:
        try:
            idx = _idx_tuple(geom, op)
        except GeometryError:
            continue
        if len(idx) != len(op.shape):
            continue
        idx_of[op.name] = (op, idx)
        nb = op.nblocks()
        for d, e in enumerate(idx):
            ok = True
            for point in _iter_grid(geom.grid):
                vals = concrete_all(e, point)
                if any(not 0 <= x <= nb[d] - 1 for x in vals):
                    ok = False
                    break
            verdicts[("inbounds", op.name, d)] = ok
        for li, lk in enumerate(_Verifier._lookups(idx)):
            table = lk.args[0]
            for k, ie in enumerate(lk.args[1]):
                ok = True
                for point in _iter_grid(geom.grid):
                    vals = concrete_all(ie, point)
                    if any(not 0 <= x <= table.shape[k] - 1 for x in vals):
                        ok = False
                        break
                verdicts[("lookup", op.name, li, k)] = ok
    for op in geom.out_operands:
        if op.name not in idx_of:
            continue
        _, idx = idx_of[op.name]
        fv = set()
        for e in idx:
            fv |= free_vars(e)
        revisit = [d for d in range(len(geom.grid))
                   if f"g{d}" not in fv and geom.grid[d] > 1]
        sem = geom.dimension_semantics
        key = ("coverage", op.name)
        if any(sem is not None and d < len(sem) and sem[d] == "parallel"
               for d in revisit):
            verdicts[key] = "double"
            continue
        reduced = [d for d in range(len(geom.grid)) if d not in revisit]
        nb = op.nblocks()
        counts: Dict[tuple, int] = {}
        bad = None
        for point in _iter_grid(geom.grid, reduced):
            vals = []
            for e in idx:
                vs = concrete_all(e, point)
                if len(vs) != 1:
                    bad = "nondeterministic"
                    break
                vals.append(next(iter(vs)))
            if bad:
                break
            counts[tuple(vals)] = counts.get(tuple(vals), 0) + 1
        if bad:
            verdicts[key] = None
            continue
        if any(n > 1 for n in counts.values()):
            verdicts[key] = "double"
        elif any(tuple(p[f"g{d}"] for d in range(len(nb))) not in counts
                 for p in _iter_grid(nb)):
            verdicts[key] = "hole"
        else:
            verdicts[key] = "exact"
    return verdicts
