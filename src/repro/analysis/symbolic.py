"""hornshape symbolic core: expressions, abstract domains, interpreter.

Three layers, all jax-free:

1. **Symbolic expressions** — ``Sym`` (integer) / ``SymBool`` trees built by
   operator overloading, so a Pallas ``index_map`` lambda evaluated on
   ``Sym`` grid variables yields the exact expression the DMA engine will
   compute (including ``jnp.where``/``jnp.minimum`` clamps and block-table
   ``lookup`` gathers).
2. **Abstract domains** — interval bounds via affine normalization (so
   ``g - g`` cancels exactly) plus a congruence domain ``(m, r)`` (value
   ≡ r mod m; ``m == 0`` means the exact constant ``r``).  ``prove``
   decides a ``SymBool`` three-valued: True / False / None (inconclusive).
3. **A restricted-Python mini-interpreter** — abstractly executes a kernel
   *wrapper* function (the Python that builds grids and BlockSpecs) on
   ``FakeArray``/``Table`` arguments, intercepting ``pl.pallas_call`` to
   capture the full launch geometry without ever importing jax.  The
   captured ``index_map`` closures are then re-entered with ``Sym`` grid
   indices by ``blockspec_verify``.

Soundness contract: a ``prove(...) is True`` verdict is a proof over *all*
concrete grid points (interval/congruence are over-approximations); the
exact ground truth for any geometry is ``concrete_all`` enumeration, which
``blockspec_verify`` falls back to whenever the symbolic layer is
inconclusive.  ``Table`` lookups contribute their declared value range
``[lo, hi]``; enumeration substitutes both endpoints.
"""
from __future__ import annotations

import ast
import math
from typing import Dict, List, Optional, Tuple

_INT_OPS = ("var", "const", "add", "sub", "mul", "neg", "floordiv", "mod",
            "min", "max", "where", "lookup")
_BOOL_OPS = ("lt", "le", "gt", "ge", "eq", "ne", "and", "or", "not", "bconst")


class AnalysisError(Exception):
    """The mini-interpreter hit code it cannot soundly abstract."""


# --------------------------------------------------------------------------
# expression nodes
# --------------------------------------------------------------------------
class Sym:
    """Integer-valued symbolic expression.  Identity-hashed: use ``seq``
    for structural equality, ``==`` builds a SymBool."""
    __slots__ = ("op", "args")

    def __init__(self, op: str, *args):
        assert op in _INT_OPS, op
        self.op = op
        self.args = args

    # -- construction helpers ------------------------------------------
    def __add__(self, o):
        return _binop("add", self, o)

    __radd__ = __add__

    def __sub__(self, o):
        return _binop("sub", self, o)

    def __rsub__(self, o):
        return _binop("sub", o, self)

    def __mul__(self, o):
        return _binop("mul", self, o)

    __rmul__ = __mul__

    def __floordiv__(self, o):
        return _binop("floordiv", self, o)

    def __rfloordiv__(self, o):
        return _binop("floordiv", o, self)

    def __mod__(self, o):
        return _binop("mod", self, o)

    def __rmod__(self, o):
        return _binop("mod", o, self)

    def __neg__(self):
        return Sym("neg", self)

    def __lt__(self, o):
        return SymBool("lt", self, sym(o))

    def __le__(self, o):
        return SymBool("le", self, sym(o))

    def __gt__(self, o):
        return SymBool("gt", self, sym(o))

    def __ge__(self, o):
        return SymBool("ge", self, sym(o))

    def __eq__(self, o):  # noqa: D105 — symbolic equality, not identity
        return SymBool("eq", self, sym(o))

    def __ne__(self, o):
        return SymBool("ne", self, sym(o))

    __hash__ = object.__hash__

    def __repr__(self):
        if self.op == "var":
            return self.args[0]
        if self.op == "const":
            return str(self.args[0])
        if self.op == "lookup":
            table, idx = self.args
            return f"{table.name}[{', '.join(map(repr, idx))}]"
        return f"{self.op}({', '.join(map(repr, self.args))})"


class SymBool:
    __slots__ = ("op", "args")

    def __init__(self, op: str, *args):
        assert op in _BOOL_OPS, op
        self.op = op
        self.args = args

    def __and__(self, o):
        return SymBool("and", self, _symbool(o))

    __rand__ = __and__

    def __or__(self, o):
        return SymBool("or", self, _symbool(o))

    __ror__ = __or__

    def __invert__(self):
        return SymBool("not", self)

    def __bool__(self):
        raise AnalysisError(
            "symbolic boolean used in concrete control flow — use "
            "jnp.where / s_where instead of `if`")

    def __repr__(self):
        return f"{self.op}({', '.join(map(repr, self.args))})"


def sym(x) -> Sym:
    if isinstance(x, Sym):
        return x
    if isinstance(x, bool):
        return Sym("const", int(x))
    if isinstance(x, int):
        return Sym("const", x)
    if isinstance(x, float) and x == int(x):
        return Sym("const", int(x))
    raise AnalysisError(f"cannot lift {x!r} into a symbolic integer")


def _symbool(x) -> SymBool:
    if isinstance(x, SymBool):
        return x
    if isinstance(x, bool):
        return SymBool("bconst", x)
    raise AnalysisError(f"cannot lift {x!r} into a symbolic boolean")


def _binop(op: str, a, b):
    a, b = sym(a), sym(b)
    if a.op == "const" and b.op == "const":
        x, y = a.args[0], b.args[0]
        return Sym("const", {
            "add": lambda: x + y, "sub": lambda: x - y,
            "mul": lambda: x * y, "floordiv": lambda: x // y,
            "mod": lambda: x % y, "min": lambda: min(x, y),
            "max": lambda: max(x, y)}[op]())
    return Sym(op, a, b)


def var(name: str) -> Sym:
    return Sym("var", name)


def const(v: int) -> Sym:
    return Sym("const", int(v))


def s_min(a, b) -> Sym:
    return _binop("min", a, b)


def s_max(a, b) -> Sym:
    return _binop("max", a, b)


def s_where(cond, a, b) -> Sym:
    if isinstance(cond, bool):
        return sym(a) if cond else sym(b)
    return Sym("where", _symbool(cond), sym(a), sym(b))


def s_clip(x, lo, hi) -> Sym:
    return s_min(s_max(x, lo), hi)


def seq(a, b) -> bool:
    """Structural equality (``==`` on Sym builds a SymBool instead)."""
    a, b = sym(a), sym(b)
    if a.op != b.op:
        return False
    if a.op in ("var", "const"):
        return a.args == b.args
    if a.op == "lookup":
        ta, ia = a.args
        tb, ib = b.args
        return ta is tb and len(ia) == len(ib) \
            and all(seq(x, y) for x, y in zip(ia, ib))
    return len(a.args) == len(b.args) \
        and all(seq(x, y) for x, y in zip(a.args, b.args))


def free_vars(e) -> set:
    out: set = set()
    stack = [e]
    while stack:
        n = stack.pop()
        if isinstance(n, Sym):
            if n.op == "var":
                out.add(n.args[0])
            elif n.op == "lookup":
                stack.extend(n.args[1])
            else:
                stack.extend(n.args)
        elif isinstance(n, SymBool):
            stack.extend(a for a in n.args if isinstance(a, (Sym, SymBool)))
    return out


def lookups_in(e) -> List[Sym]:
    """Every lookup node anywhere in ``e`` (including where-conditions)."""
    out: List[Sym] = []
    stack = [e]
    while stack:
        n = stack.pop()
        if isinstance(n, Sym):
            if n.op == "lookup":
                out.append(n)
                stack.extend(n.args[1])
            else:
                stack.extend(n.args)
        elif isinstance(n, SymBool):
            stack.extend(a for a in n.args if isinstance(a, (Sym, SymBool)))
    return out


# --------------------------------------------------------------------------
# abstract values the interpreter manipulates
# --------------------------------------------------------------------------
class Table:
    """Scalar-prefetch operand (block table / lengths): an int array whose
    *contents* are abstract but bounded to the declared ``[lo, hi]``."""

    def __init__(self, name: str, shape: Tuple[int, ...],
                 lo: int = 0, hi: int = 0):
        self.name = name
        self.shape = tuple(shape)
        self.lo, self.hi = lo, hi

    @property
    def ndim(self):
        return len(self.shape)

    def astype(self, _dtype):
        return self

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        if len(idx) > len(self.shape):
            raise AnalysisError(
                f"table {self.name} indexed with {len(idx)} subscripts "
                f"but has rank {len(self.shape)}")
        return Sym("lookup", self, tuple(sym(i) for i in idx))

    def __repr__(self):
        return f"Table({self.name}, {self.shape}, [{self.lo},{self.hi}])"


class FakeArray:
    """Shape/dtype-only stand-in for a jax array."""

    def __init__(self, shape: Tuple[int, ...], dtype: str = "float32"):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = dtype

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        return math.prod(self.shape)

    def astype(self, dtype):
        return FakeArray(self.shape, _dtype_name(dtype))

    def reshape(self, *dims):
        if len(dims) == 1 and isinstance(dims[0], (tuple, list)):
            dims = tuple(dims[0])
        dims = tuple(int(d) for d in dims)
        if -1 in dims:
            known = math.prod(d for d in dims if d != -1)
            dims = tuple(self.size // known if d == -1 else d for d in dims)
        if math.prod(dims) != self.size:
            raise AnalysisError(
                f"reshape {self.shape} -> {dims}: element count mismatch")
        return FakeArray(dims, self.dtype)

    def transpose(self, *perm):
        if len(perm) == 1 and isinstance(perm[0], (tuple, list)):
            perm = tuple(perm[0])
        if sorted(perm) != list(range(self.ndim)):
            raise AnalysisError(f"bad transpose {perm} for rank {self.ndim}")
        return FakeArray(tuple(self.shape[p] for p in perm), self.dtype)

    def __repr__(self):
        return f"FakeArray({self.shape}, {self.dtype})"


def _dtype_name(d) -> str:
    if isinstance(d, str):
        return d.split(".")[-1]
    if isinstance(d, FakeArray):
        return d.dtype
    return str(d)


class BlockSpecV:
    def __init__(self, block_shape=None, index_map=None, memory_space=None):
        self.block_shape = tuple(block_shape) if block_shape is not None \
            else None
        self.index_map = index_map
        self.memory_space = memory_space


class GridSpecV:
    def __init__(self, num_scalar_prefetch=0, grid=(), in_specs=None,
                 out_specs=None, scratch_shapes=None):
        self.num_scalar_prefetch = num_scalar_prefetch
        self.grid = tuple(grid)
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.scratch_shapes = scratch_shapes


class ScratchV:
    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(shape)
        self.dtype = _dtype_name(dtype)


class ShapeDtypeV:
    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = _dtype_name(dtype)


class Capture:
    """One pl.pallas_call site: launch kwargs + the concrete call args."""

    def __init__(self, kernel, kwargs, lineno):
        self.kernel = kernel
        self.kwargs = kwargs
        self.lineno = lineno
        self.args: list = []


class PallasCaller:
    def __init__(self, capture: Capture, sink: list):
        self.capture = capture
        self.sink = sink

    def __call__(self, *args):
        self.capture.args = list(args)
        self.sink.append(self.capture)
        out_shape = self.capture.kwargs.get("out_shape")
        if isinstance(out_shape, (list, tuple)):
            return [FakeArray(o.shape, o.dtype) for o in out_shape]
        if out_shape is None:
            raise AnalysisError("pallas_call without out_shape")
        return FakeArray(out_shape.shape, out_shape.dtype)


# --------------------------------------------------------------------------
# interval bounds via affine normalization
# --------------------------------------------------------------------------
_NEG = -(1 << 62)
_POS = 1 << 62

Env = Dict[str, Tuple[int, int]]   # var name -> inclusive range


def _linearize(e: Sym):
    """-> (const, {var: coeff}, [(coeff, opaque Sym)]) with cancellation."""
    if e.op == "const":
        return e.args[0], {}, []
    if e.op == "var":
        return 0, {e.args[0]: 1}, []
    if e.op == "neg":
        c, v, o = _linearize(e.args[0])
        return -c, {k: -x for k, x in v.items()}, [(-x, a) for x, a in o]
    if e.op in ("add", "sub"):
        c1, v1, o1 = _linearize(e.args[0])
        c2, v2, o2 = _linearize(e.args[1])
        s = 1 if e.op == "add" else -1
        v = dict(v1)
        for k, x in v2.items():
            v[k] = v.get(k, 0) + s * x
        return (c1 + s * c2, {k: x for k, x in v.items() if x},
                o1 + [(s * x, a) for x, a in o2])
    if e.op == "mul":
        for a, b in (e.args, e.args[::-1]):
            ca, va, oa = _linearize(a)
            if not va and not oa:                  # pure constant side
                cb, vb, ob = _linearize(b)
                return (ca * cb, {k: ca * x for k, x in vb.items() if ca * x},
                        [(ca * x, at) for x, at in ob if ca * x])
    return 0, {}, [(1, e)]


def _scaled(coeff: int, lo: int, hi: int) -> Tuple[int, int]:
    a, b = coeff * lo, coeff * hi
    return (min(a, b), max(a, b))


def bounds(e, env: Env) -> Tuple[int, int]:
    """Inclusive interval of ``e`` over ``env`` var ranges (sound)."""
    e = sym(e)
    c, vs, ops = _linearize(e)
    lo = hi = c
    for name, coeff in vs.items():
        if name not in env:
            return (_NEG, _POS)
        vlo, vhi = env[name]
        a, b = _scaled(coeff, vlo, vhi)
        lo, hi = lo + a, hi + b
    for coeff, atom in ops:
        alo, ahi = _atom_bounds(atom, env)
        if alo <= _NEG or ahi >= _POS:
            return (_NEG, _POS)
        a, b = _scaled(coeff, alo, ahi)
        lo, hi = lo + a, hi + b
    return lo, hi


def _atom_bounds(e: Sym, env: Env) -> Tuple[int, int]:
    if e.op == "lookup":
        t = e.args[0]
        return (t.lo, t.hi)
    if e.op in ("min", "max"):
        a = bounds(e.args[0], env)
        b = bounds(e.args[1], env)
        if e.op == "min":
            return (min(a[0], b[0]), min(a[1], b[1]))
        return (max(a[0], b[0]), max(a[1], b[1]))
    if e.op == "where":
        cond, x, y = e.args
        v = prove(cond, env)
        if v is True:
            return bounds(x, env)
        if v is False:
            return bounds(y, env)
        a, b = bounds(x, env), bounds(y, env)
        return (min(a[0], b[0]), max(a[1], b[1]))
    if e.op == "floordiv":
        (alo, ahi) = bounds(e.args[0], env)
        d = e.args[1]
        if d.op == "const" and d.args[0] > 0 and alo > _NEG and ahi < _POS:
            return (alo // d.args[0], ahi // d.args[0])
        return (_NEG, _POS)
    if e.op == "mod":
        (alo, ahi) = bounds(e.args[0], env)
        d = e.args[1]
        if d.op == "const" and d.args[0] > 0:
            dd = d.args[0]
            if alo > _NEG and ahi < _POS and alo // dd == ahi // dd:
                return (alo % dd, ahi % dd)   # one period: exact
            if alo >= 0:
                return (0, dd - 1)
            return (-(dd - 1), dd - 1)
        return (_NEG, _POS)
    if e.op == "mul":
        a, b = bounds(e.args[0], env), bounds(e.args[1], env)
        if min(a + b) <= _NEG or max(a + b) >= _POS:
            return (_NEG, _POS)
        corners = [x * y for x in a for y in b]
        return (min(corners), max(corners))
    # add/sub/neg atoms never reach here (linearized away); be safe:
    return bounds(e, env) if e.op in ("add", "sub", "neg", "const", "var") \
        else (_NEG, _POS)


# --------------------------------------------------------------------------
# congruence domain: value ≡ r (mod m); m == 0 means exactly r
# --------------------------------------------------------------------------
def congruence(e, env: Env) -> Tuple[int, int]:
    e = sym(e)
    if e.op == "const":
        return (0, e.args[0])
    if e.op == "var":
        lo, hi = env.get(e.args[0], (_NEG, _POS))
        if lo == hi:
            return (0, lo)
        return (1, 0)
    if e.op == "neg":
        m, r = congruence(e.args[0], env)
        return (0, -r) if m == 0 else (m, (-r) % m)
    if e.op in ("add", "sub"):
        m1, r1 = congruence(e.args[0], env)
        m2, r2 = congruence(e.args[1], env)
        s = 1 if e.op == "add" else -1
        if m1 == 0 and m2 == 0:
            return (0, r1 + s * r2)
        g = math.gcd(m1, m2)
        if g == 0:
            g = max(m1, m2)
        if g <= 1:
            return (1, 0)
        return (g, (r1 + s * r2) % g)
    if e.op == "mul":
        m1, r1 = congruence(e.args[0], env)
        m2, r2 = congruence(e.args[1], env)
        if m1 == 0 and m2 == 0:
            return (0, r1 * r2)
        if m1 == 0:
            m1, r1, m2, r2 = m2, r2, m1, r1
        # now m1 > 0; multiply by exact constant r2?
        if m2 == 0:
            c = r2
            if c == 0:
                return (0, 0)
            mm = abs(m1 * c)
            return (mm, (r1 * c) % mm) if mm > 1 else (1, 0)
        return (1, 0)
    if e.op == "floordiv":
        d = e.args[1]
        if d.op == "const" and d.args[0] > 0:
            dd = d.args[0]
            m, r = congruence(e.args[0], env)
            if m == 0:
                return (0, r // dd)
            if m % dd == 0 and 0 <= r < m:
                mm = m // dd
                return (mm, (r // dd) % mm) if mm > 1 else (1, 0)
        return (1, 0)
    if e.op == "mod":
        d = e.args[1]
        if d.op == "const" and d.args[0] > 0:
            dd = d.args[0]
            m, r = congruence(e.args[0], env)
            if m == 0:
                return (0, r % dd)
            if m % dd == 0:
                return (0, r % dd)          # x = m k + r, d | m -> x%d = r%d
            if m > 1 and dd % m == 0:
                return (m, r % m)
        return (1, 0)
    return (1, 0)   # min/max/where/lookup: no congruence info


# --------------------------------------------------------------------------
# three-valued proving
# --------------------------------------------------------------------------
def prove(b, env: Env) -> Optional[bool]:
    """True: holds for every valuation; False: fails for every valuation;
    None: inconclusive (mixed or unknown)."""
    b = _symbool(b)
    if b.op == "bconst":
        return b.args[0]
    if b.op == "not":
        v = prove(b.args[0], env)
        return None if v is None else (not v)
    if b.op == "and":
        l, r = prove(b.args[0], env), prove(b.args[1], env)
        if l is False or r is False:
            return False
        if l is True and r is True:
            return True
        return None
    if b.op == "or":
        l, r = prove(b.args[0], env), prove(b.args[1], env)
        if l is True or r is True:
            return True
        if l is False and r is False:
            return False
        return None
    a, c = sym(b.args[0]), sym(b.args[1])
    diff = Sym("sub", a, c)
    lo, hi = bounds(diff, env)
    unb = lo <= _NEG or hi >= _POS
    if b.op in ("lt", "gt", "le", "ge"):
        if b.op in ("gt", "ge"):
            lo, hi = -hi, -lo
            strict = b.op == "gt"
        else:
            strict = b.op == "lt"
        if unb:
            return None
        if (hi < 0) if strict else (hi <= 0):
            return True
        if (lo >= 0) if strict else (lo > 0):
            return False
        return None
    if b.op in ("eq", "ne"):
        want = b.op == "eq"
        if not unb:
            if lo == hi == 0:
                return want
            if lo > 0 or hi < 0:
                return not want
        m, r = congruence(diff, env)
        if m == 0:
            return want if r == 0 else (not want)
        if m > 1 and r != 0:
            return not want          # diff ≡ r ≠ 0 (mod m): never zero
        return None
    return None


# --------------------------------------------------------------------------
# exact concrete enumeration (the ground truth the property test trusts)
# --------------------------------------------------------------------------
_ENUM_CAP = 64


def concrete_all(e, point: Dict[str, int]) -> frozenset:
    """All values ``e`` can take at the concrete grid ``point``; lookups
    contribute their table's declared endpoints {lo, hi} (exact for the
    monotone clamp/guard uses the kernels make of them)."""
    e = sym(e) if not isinstance(e, SymBool) else e
    if isinstance(e, SymBool):
        return _concrete_bool(e, point)
    if e.op == "const":
        return frozenset((e.args[0],))
    if e.op == "var":
        if e.args[0] not in point:
            raise AnalysisError(f"unbound var {e.args[0]} in enumeration")
        return frozenset((point[e.args[0]],))
    if e.op == "lookup":
        t = e.args[0]
        for i, ix in enumerate(e.args[1]):
            for v in concrete_all(ix, point):
                if not 0 <= v < t.shape[i]:
                    # OOB lookups surface through the in-bounds obligations;
                    # value-wise the read is unconstrained
                    return frozenset((t.lo, t.hi))
        return frozenset((t.lo, t.hi)) if t.lo != t.hi \
            else frozenset((t.lo,))
    if e.op == "where":
        cond, a, b = e.args
        out = set()
        cv = _concrete_bool(cond, point)
        if True in cv:
            out |= concrete_all(a, point)
        if False in cv:
            out |= concrete_all(b, point)
        return _cap(out)
    if e.op == "neg":
        return _cap({-v for v in concrete_all(e.args[0], point)})
    fns = {"add": lambda x, y: x + y, "sub": lambda x, y: x - y,
           "mul": lambda x, y: x * y, "floordiv": lambda x, y: x // y,
           "mod": lambda x, y: x % y, "min": min, "max": max}
    f = fns[e.op]
    out = set()
    for x in concrete_all(e.args[0], point):
        for y in concrete_all(e.args[1], point):
            out.add(f(x, y))
    return _cap(out)


def _cap(s: set) -> frozenset:
    if len(s) > _ENUM_CAP:
        raise AnalysisError(f"value set exploded past {_ENUM_CAP}")
    return frozenset(s)


def _concrete_bool(b: SymBool, point) -> frozenset:
    if b.op == "bconst":
        return frozenset((b.args[0],))
    if b.op == "not":
        return frozenset(not v for v in _concrete_bool(b.args[0], point))
    if b.op in ("and", "or"):
        f = (lambda x, y: x and y) if b.op == "and" else (lambda x, y: x or y)
        out = set()
        for x in _concrete_bool(b.args[0], point):
            for y in _concrete_bool(b.args[1], point):
                out.add(f(x, y))
        return frozenset(out)
    fns = {"lt": lambda x, y: x < y, "le": lambda x, y: x <= y,
           "gt": lambda x, y: x > y, "ge": lambda x, y: x >= y,
           "eq": lambda x, y: x == y, "ne": lambda x, y: x != y}
    f = fns[b.op]
    out = set()
    for x in concrete_all(sym(b.args[0]), point):
        for y in concrete_all(sym(b.args[1]), point):
            out.add(f(x, y))
    return frozenset(out)


# --------------------------------------------------------------------------
# the mini-interpreter
# --------------------------------------------------------------------------
class _Return(Exception):
    def __init__(self, value):
        self.value = value


class Closure:
    def __init__(self, node, env: "Frame", interp: "Interp", name=""):
        self.node = node            # FunctionDef | Lambda
        self.env = env
        self.interp = interp
        self.name = name or getattr(node, "name", "<lambda>")

    def __call__(self, *args, **kwargs):
        return self.interp.call(self, args, kwargs)


class Partial:
    def __init__(self, fn, args, kwargs):
        self.fn, self.args, self.kwargs = fn, tuple(args), dict(kwargs)

    def __call__(self, *args, **kwargs):
        kw = dict(self.kwargs)
        kw.update(kwargs)
        return self.fn(*self.args, *args, **kw)


_DTYPE_NAMES = {
    "float64", "float32", "float16", "bfloat16", "int64", "int32", "int16",
    "int8", "int4", "uint8", "uint32", "bool_",
}


class NS:
    """Intrinsic namespace (jnp / jax / pl / pltpu / functools / lax)."""

    def __init__(self, name: str, table: Dict[str, object]):
        self._name = name
        self._table = table

    def __getattr__(self, attr):
        if attr.startswith("_"):
            raise AttributeError(attr)
        if attr in self._table:
            return self._table[attr]
        if attr in _DTYPE_NAMES:
            return attr                 # bare dtype name: comparable
        return f"{self._name}.{attr}"   # memory-space / misc token


class Frame:
    def __init__(self, parent: Optional["Frame"] = None):
        self.vars: Dict[str, object] = {}
        self.parent = parent

    def get(self, name):
        f = self
        while f is not None:
            if name in f.vars:
                return f.vars[name]
            f = f.parent
        raise AnalysisError(f"unbound name {name!r}")

    def has(self, name):
        f = self
        while f is not None:
            if name in f.vars:
                return True
            f = f.parent
        return False

    def set(self, name, value):
        self.vars[name] = value


def _jnp_where(cond, a, b):
    if isinstance(cond, bool):
        return a if cond else b
    return s_where(cond, a, b)


def _jnp_minimum(a, b):
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return min(a, b)
    return s_min(a, b)


def _jnp_maximum(a, b):
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return max(a, b)
    return s_max(a, b)


def _jnp_clip(x, lo, hi):
    return _jnp_minimum(_jnp_maximum(x, lo), hi)


def _jit(fn=None, **_kw):
    if fn is None:
        return lambda f: f
    return fn


def _shape_struct(shape, dtype):
    return ShapeDtypeV(shape, dtype)


class Interp:
    """Abstract interpreter for kernel-wrapper Python.

    Executes module top level (constants + defs; imports skipped), then
    ``call``-s a wrapper on ``FakeArray``/``Table`` args.  Every
    ``pl.pallas_call`` invocation lands in ``self.captures``.
    """

    def __init__(self):
        self.captures: List[Capture] = []
        self.globals = Frame()
        jnp_tbl = {
            "where": _jnp_where, "minimum": _jnp_minimum,
            "maximum": _jnp_maximum, "clip": _jnp_clip,
        }
        lax_tbl: Dict[str, object] = {}
        jax_tbl = {
            "jit": _jit,
            "ShapeDtypeStruct": _shape_struct,
            "numpy": NS("jnp", jnp_tbl),
            "lax": NS("lax", lax_tbl),
        }
        pl_tbl = {
            "BlockSpec": BlockSpecV,
            "pallas_call": self._pallas_call,
        }
        pltpu_tbl = {
            "PrefetchScalarGridSpec": GridSpecV,
            "VMEM": ScratchV,
            "SMEM": "pltpu.SMEM",
            "ANY": "pltpu.ANY",
            "CompilerParams": lambda **kw: dict(kw),
        }
        ft_tbl = {"partial": lambda fn, *a, **kw: Partial(fn, a, kw)}
        self.namespaces = {
            "jnp": NS("jnp", jnp_tbl), "jax": NS("jax", jax_tbl),
            "lax": NS("lax", lax_tbl), "pl": NS("pl", pl_tbl),
            "pltpu": NS("pltpu", pltpu_tbl),
            "functools": NS("functools", ft_tbl),
            "np": NS("np", {}), "partial": ft_tbl["partial"],
        }
        self.builtins = {
            "range": range, "len": len, "max": max, "min": min, "abs": abs,
            "int": int, "sum": sum, "sorted": sorted, "tuple": tuple,
            "list": list, "enumerate": enumerate, "zip": zip,
            "ValueError": ValueError, "AssertionError": AssertionError,
            "True": True, "False": False, "None": None,
        }
        self._lineno = 0

    # -- intrinsics ----------------------------------------------------
    def _pallas_call(self, kernel, **kwargs):
        cap = Capture(kernel, kwargs, self._lineno)
        return PallasCaller(cap, self.captures)

    # -- module / function entry ---------------------------------------
    def run_module(self, tree: ast.Module) -> Frame:
        env = Frame(self.globals)
        for name, ns in self.namespaces.items():
            env.set(name, ns)
        for stmt in tree.body:
            self._stmt(stmt, env)
        return env

    def call(self, fn, args=(), kwargs=None):
        kwargs = kwargs or {}
        while isinstance(fn, Partial):
            kwargs = {**fn.kwargs, **kwargs}
            args = (*fn.args, *args)
            fn = fn.fn
        if isinstance(fn, Closure):
            return self._call_closure(fn, args, kwargs)
        if callable(fn):
            return fn(*args, **kwargs)
        raise AnalysisError(f"not callable: {fn!r}")

    def _call_closure(self, cl: Closure, args, kwargs):
        node = cl.node
        frame = Frame(cl.env)
        a = node.args
        names = [p.arg for p in a.posonlyargs + a.args]
        pos = list(args)
        n_named = len(names)
        bound: Dict[str, object] = {}
        for i, name in enumerate(names):
            if i < len(pos):
                bound[name] = pos[i]
        extra = pos[n_named:]
        if a.vararg is not None:
            bound[a.vararg.arg] = tuple(extra)
        elif extra:
            raise AnalysisError(
                f"{cl.name}() takes {n_named} positional args, got "
                f"{len(pos)}")
        kw_names = [p.arg for p in a.kwonlyargs]
        for k, v in kwargs.items():
            if k in names or k in kw_names:
                if k in bound:
                    raise AnalysisError(f"duplicate arg {k!r} to {cl.name}")
                bound[k] = v
            elif a.kwarg is not None:
                bound.setdefault(a.kwarg.arg, {})
                bound[a.kwarg.arg][k] = v
            else:
                raise AnalysisError(f"unexpected kwarg {k!r} to {cl.name}")
        # defaults
        defaults = a.defaults
        for i, d in enumerate(defaults):
            name = names[n_named - len(defaults) + i]
            if name not in bound:
                bound[name] = self._expr(d, cl.env)
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg not in bound:
                if d is None:
                    raise AnalysisError(
                        f"missing kwonly arg {p.arg!r} to {cl.name}")
                bound[p.arg] = self._expr(d, cl.env)
        missing = [n for n in names + kw_names if n not in bound]
        if missing:
            raise AnalysisError(f"missing args {missing} to {cl.name}")
        for k, v in bound.items():
            frame.set(k, v)
        if isinstance(node, ast.Lambda):
            return self._expr(node.body, frame)
        try:
            for stmt in node.body:
                self._stmt(stmt, frame)
        except _Return as r:
            return r.value
        return None

    # -- statements ----------------------------------------------------
    def _stmt(self, node, env: Frame):
        self._lineno = getattr(node, "lineno", self._lineno)
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            return
        if isinstance(node, (ast.FunctionDef,)):
            fn: object = Closure(node, env, self, node.name)
            for dec in reversed(node.decorator_list):
                fn = self.call(self._expr(dec, env), (fn,))
            env.set(node.name, fn)
            return
        if isinstance(node, ast.ClassDef):
            return                                    # not needed; skip
        if isinstance(node, ast.Assign):
            value = self._expr(node.value, env)
            for tgt in node.targets:
                self._assign(tgt, value, env)
            return
        if isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self._assign(node.target, self._expr(node.value, env), env)
            return
        if isinstance(node, ast.AugAssign):
            if not isinstance(node.target, ast.Name):
                raise AnalysisError("augmented assign to non-name")
            cur = env.get(node.target.id)
            val = self._expr(node.value, env)
            env.set(node.target.id, self._binary(node.op, cur, val))
            return
        if isinstance(node, ast.Expr):
            self._expr(node.value, env)
            return
        if isinstance(node, ast.Return):
            raise _Return(self._expr(node.value, env)
                          if node.value is not None else None)
        if isinstance(node, ast.If):
            body = node.body if self._concrete_cond(node.test, env) \
                else node.orelse
            for s in body:
                self._stmt(s, env)
            return
        if isinstance(node, ast.While):
            guard = 0
            while self._concrete_cond(node.test, env):
                for s in node.body:
                    self._stmt(s, env)
                guard += 1
                if guard > 10_000:
                    raise AnalysisError("while loop did not terminate")
            return
        if isinstance(node, ast.For):
            it = self._expr(node.iter, env)
            if not isinstance(it, (range, list, tuple)):
                raise AnalysisError(f"cannot iterate {it!r}")
            for v in it:
                self._assign(node.target, v, env)
                for s in node.body:
                    self._stmt(s, env)
            for s in node.orelse:
                self._stmt(s, env)
            return
        if isinstance(node, ast.Assert):
            try:
                ok = self._concrete_cond(node.test, env)
            except AnalysisError:
                return                  # symbolic assert: cannot discharge
            if not ok:
                raise AnalysisError(
                    f"assert failed at line {node.lineno}")
            return
        if isinstance(node, ast.Raise):
            raise AnalysisError(f"raise reached at line {node.lineno}")
        if isinstance(node, ast.Pass):
            return
        if isinstance(node, ast.Global):
            return
        raise AnalysisError(
            f"unsupported statement {type(node).__name__} at line "
            f"{getattr(node, 'lineno', '?')}")

    def _assign(self, tgt, value, env: Frame):
        if isinstance(tgt, ast.Name):
            env.set(tgt.id, value)
            return
        if isinstance(tgt, (ast.Tuple, ast.List)):
            vals = list(value)
            if len(vals) != len(tgt.elts):
                raise AnalysisError(
                    f"unpack mismatch: {len(tgt.elts)} targets, "
                    f"{len(vals)} values")
            for t, v in zip(tgt.elts, vals):
                self._assign(t, v, env)
            return
        if isinstance(tgt, ast.Starred):
            raise AnalysisError("starred assignment unsupported")
        raise AnalysisError(
            f"unsupported assign target {type(tgt).__name__}")

    def _concrete_cond(self, test, env) -> bool:
        v = self._expr(test, env)
        if isinstance(v, (Sym, SymBool)):
            raise AnalysisError(
                f"symbolic condition in concrete control flow at line "
                f"{getattr(test, 'lineno', '?')}")
        return bool(v)

    # -- expressions ---------------------------------------------------
    def _expr(self, node, env: Frame):
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            if env.has(node.id):
                return env.get(node.id)
            if node.id in self.builtins:
                return self.builtins[node.id]
            raise AnalysisError(f"unbound name {node.id!r} at line "
                                f"{getattr(node, 'lineno', '?')}")
        if isinstance(node, ast.Tuple):
            return tuple(self._expr(e, env) for e in node.elts)
        if isinstance(node, ast.List):
            return [self._expr(e, env) for e in node.elts]
        if isinstance(node, ast.Dict):
            return {self._expr(k, env): self._expr(v, env)
                    for k, v in zip(node.keys, node.values)}
        if isinstance(node, ast.BinOp):
            return self._binary(node.op, self._expr(node.left, env),
                                self._expr(node.right, env))
        if isinstance(node, ast.UnaryOp):
            v = self._expr(node.operand, env)
            if isinstance(node.op, ast.USub):
                return -v
            if isinstance(node.op, ast.UAdd):
                return v
            if isinstance(node.op, ast.Not):
                if isinstance(v, SymBool):
                    return ~v
                return not v
            if isinstance(node.op, ast.Invert):
                if isinstance(v, SymBool):
                    return ~v
                return ~v
        if isinstance(node, ast.BoolOp):
            vals = [self._expr(v, env) for v in node.values]
            if any(isinstance(v, (Sym, SymBool)) for v in vals):
                out = _symbool(vals[0]) if not isinstance(vals[0], Sym) \
                    else (sym(vals[0]) != 0)
                for v in vals[1:]:
                    v = _symbool(v) if not isinstance(v, Sym) \
                        else (sym(v) != 0)
                    out = (out & v) if isinstance(node.op, ast.And) \
                        else (out | v)
                return out
            if isinstance(node.op, ast.And):
                out = vals[0]
                for v in vals[1:]:
                    out = out and v
                return out
            out = vals[0]
            for v in vals[1:]:
                out = out or v
            return out
        if isinstance(node, ast.Compare):
            left = self._expr(node.left, env)
            result: object = True
            for op, cmp in zip(node.ops, node.comparators):
                right = self._expr(cmp, env)
                step = self._compare(op, left, right)
                if isinstance(step, SymBool):
                    if result is not True:
                        raise AnalysisError("chained symbolic compare")
                    result = step
                else:
                    if isinstance(result, SymBool):
                        raise AnalysisError("chained symbolic compare")
                    result = result and step
                    if result is False:
                        return False
                left = right
            return result
        if isinstance(node, ast.IfExp):
            return self._expr(node.body, env) \
                if self._concrete_cond(node.test, env) \
                else self._expr(node.orelse, env)
        if isinstance(node, ast.Lambda):
            return Closure(node, env, self)
        if isinstance(node, ast.Call):
            fn = self._expr(node.func, env)
            args = []
            for a in node.args:
                if isinstance(a, ast.Starred):
                    args.extend(self._expr(a.value, env))
                else:
                    args.append(self._expr(a, env))
            kwargs = {}
            for k in node.keywords:
                if k.arg is None:
                    kwargs.update(self._expr(k.value, env))
                else:
                    kwargs[k.arg] = self._expr(k.value, env)
            self._lineno = node.lineno
            return self.call(fn, args, kwargs)
        if isinstance(node, ast.Attribute):
            base = self._expr(node.value, env)
            return self._attr(base, node.attr)
        if isinstance(node, ast.Subscript):
            base = self._expr(node.value, env)
            idx = self._slice(node.slice, env)
            return self._subscript(base, idx)
        if isinstance(node, ast.ListComp):
            return self._comp(node, env)
        if isinstance(node, ast.GeneratorExp):
            return self._comp(node, env)
        if isinstance(node, ast.JoinedStr):
            parts = []
            for v in node.values:
                if isinstance(v, ast.Constant):
                    parts.append(str(v.value))
                else:
                    parts.append(str(self._expr(v.value, env)))
            return "".join(parts)
        raise AnalysisError(
            f"unsupported expression {type(node).__name__} at line "
            f"{getattr(node, 'lineno', '?')}")

    def _comp(self, node, env: Frame):
        if len(node.generators) != 1:
            raise AnalysisError("nested comprehensions unsupported")
        gen = node.generators[0]
        it = self._expr(gen.iter, env)
        out = []
        for v in it:
            frame = Frame(env)
            self._assign(gen.target, v, frame)
            if all(self._concrete_cond(c, frame) for c in gen.ifs):
                out.append(self._expr(node.elt, frame))
        return out

    def _slice(self, node, env: Frame):
        if isinstance(node, ast.Slice):
            return slice(
                self._expr(node.lower, env) if node.lower else None,
                self._expr(node.upper, env) if node.upper else None,
                self._expr(node.step, env) if node.step else None)
        if isinstance(node, ast.Tuple):
            return tuple(self._slice(e, env) for e in node.elts)
        return self._expr(node, env)

    def _subscript(self, base, idx):
        if isinstance(base, Table):
            return base[idx]
        if isinstance(base, (tuple, list, str, dict, range)):
            return base[idx]
        if isinstance(base, FakeArray):
            raise AnalysisError("value indexing of a FakeArray (only "
                                ".shape / .dtype are abstracted)")
        raise AnalysisError(f"cannot subscript {base!r}")

    def _attr(self, base, attr):
        if isinstance(base, NS):
            return getattr(base, attr)
        if isinstance(base, (FakeArray, Table, ScratchV, ShapeDtypeV,
                             BlockSpecV, GridSpecV)):
            if attr in ("shape", "dtype", "ndim", "size", "astype",
                        "reshape", "transpose", "block_shape", "index_map",
                        "memory_space", "grid", "in_specs", "out_specs",
                        "scratch_shapes", "num_scalar_prefetch", "name",
                        "lo", "hi"):
                return getattr(base, attr)
            raise AnalysisError(f"unsupported attribute .{attr} on "
                                f"{type(base).__name__}")
        if isinstance(base, list) and attr in ("append", "extend", "pop"):
            return getattr(base, attr)
        if isinstance(base, str):
            # dtype-token attribute chains like jnp.float32 -> "jnp.float32"
            return f"{base}.{attr}"
        raise AnalysisError(f"unsupported attribute .{attr} on {base!r}")

    def _binary(self, op, a, b):
        symbolic = isinstance(a, (Sym, SymBool)) or isinstance(
            b, (Sym, SymBool))
        if isinstance(op, ast.Add):
            return a + b
        if isinstance(op, ast.Sub):
            return a - b
        if isinstance(op, ast.Mult):
            return a * b
        if isinstance(op, ast.FloorDiv):
            return a // b
        if isinstance(op, ast.Mod):
            return a % b
        if isinstance(op, ast.Div):
            if symbolic:
                raise AnalysisError("true division on symbolic values")
            return a / b
        if isinstance(op, ast.Pow):
            if symbolic:
                raise AnalysisError("pow on symbolic values")
            return a ** b
        raise AnalysisError(f"unsupported operator {type(op).__name__}")

    def _compare(self, op, a, b):
        if isinstance(op, ast.Is):
            return a is b
        if isinstance(op, ast.IsNot):
            return a is not b
        if isinstance(op, ast.In):
            return a in b
        if isinstance(op, ast.NotIn):
            return a not in b
        symbolic = isinstance(a, Sym) or isinstance(b, Sym)
        if symbolic:
            a, b = sym(a), sym(b)
            tbl = {ast.Lt: "lt", ast.LtE: "le", ast.Gt: "gt", ast.GtE: "ge",
                   ast.Eq: "eq", ast.NotEq: "ne"}
            return SymBool(tbl[type(op)], a, b)
        if isinstance(op, ast.Lt):
            return a < b
        if isinstance(op, ast.LtE):
            return a <= b
        if isinstance(op, ast.Gt):
            return a > b
        if isinstance(op, ast.GtE):
            return a >= b
        if isinstance(op, ast.Eq):
            return a == b
        if isinstance(op, ast.NotEq):
            return a != b
        raise AnalysisError(f"unsupported compare {type(op).__name__}")


def interpret_file(path_or_src, path: str = "<string>"):
    """Parse + abstractly execute a module; -> (Interp, module Frame)."""
    src = path_or_src
    tree = ast.parse(src, filename=path)
    interp = Interp()
    env = interp.run_module(tree)
    return interp, env
