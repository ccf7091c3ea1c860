"""hornshape driver: prove BlockSpec/grid safety for the repo's kernels.

``python -m repro.analysis.hornshape`` (no args) abstractly executes every
registered kernel wrapper under ``symbolic.Interp`` at several concrete
geometry instantiations (ragged tails, multi-page steps, GQA grouping,
quantized sidecars, fused verify windows), captures each ``pallas_call``,
and runs :mod:`repro.analysis.blockspec_verify` over it.  Exit 0 when every
obligation is proved, 1 with findings (each carrying a counterexample grid
point), 2 on driver error.

What is *proved* vs *linted*: for a given shape instantiation the grid-
index quantifier is discharged symbolically (or by exhaustive enumeration
— both sound); the shape-parameter quantifier is discharged by the
representative instantiations below, chosen to hit every branch of the
wrappers (ragged / divisible, pps 1 / >1, quantized on / off, window on /
off).  That is strictly stronger than the HL3xx syntactic checks but
weaker than a proof over all shapes.

Explicit file arguments may instead carry their own geometry declarations:
a module-level literal

    HORNSHAPE = {"entries": [
        {"fn": "my_kernel",
         "args": [{"array": [8, 16]}, {"table": "bt", "shape": [4],
                   "range": [0, 7]}, 4],
         "kwargs": {"block": 4},
         "null_page": ["bt", 0]},            # optional
    ]}

(the seeded-violation fixtures under ``tests/hornlint_fixtures/`` use
this).  ``serve.py --sanitize`` reuses :func:`crosscheck_paged_geometry`
to re-verify the *serving engine's actual* paged-attention geometry at
runtime and cross-check the symbolic verdicts against brute-force
enumeration for one tick.
"""
from __future__ import annotations

import argparse
import ast
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from repro.analysis.blockspec_verify import (GeometryError, Report,
                                             brute_force,
                                             geometry_from_capture, verify)
from repro.analysis.symbolic import (AnalysisError, FakeArray, Interp,
                                     Table, interpret_file)


# --------------------------------------------------------------------------
# built-in geometry registry for the four committed kernel packages
# --------------------------------------------------------------------------
def _paged_entries() -> List[dict]:
    def decode(B, H, KH, D, psize, P, maxp, **kw):
        args = [FakeArray((B, H, D)),
                FakeArray((P, KH, psize, D), kw.pop("kv_dtype", "bfloat16")),
                FakeArray((P, KH, psize, D), "bfloat16"),
                Table("block_tables", (B, maxp), 0, P - 1),
                Table("lengths", (B,), 0, maxp * psize)]
        if kw.pop("quantized", False):
            args[1] = FakeArray((P, KH, psize, D), "int8")
            args[2] = FakeArray((P, KH, psize, D), "int8")
            kw["k_scale"] = FakeArray((P, KH))
            kw["v_scale"] = FakeArray((P, KH))
        return args, {"scale": 0.5, **kw}

    def chunk(B, C, H, KH, D, psize, P, maxp, S_w=0, **kw):
        kv = "int8" if kw.pop("quantized", False) else "bfloat16"
        args = [FakeArray((B, C, H, D)),
                FakeArray((P, KH, psize, D), kv),
                FakeArray((P, KH, psize, D), kv),
                Table("block_tables", (B, maxp), 0, P - 1),
                Table("starts", (B,), 0, maxp * psize),
                Table("chunk_lens", (B,), 0, C)]
        if S_w:
            kw["logit_index"] = Table("logit_index", (B, S_w), 0, C - 1)
        if kv == "int8":
            kw["k_scale"] = FakeArray((P, KH))
            kw["v_scale"] = FakeArray((P, KH))
        return args, {"scale": 0.5, **kw}

    return [
        # ragged page tail (maxp % pps != 0) + multi-page grid steps
        {"fn": "paged_attention", "label": "decode/pps2-ragged",
         "build": lambda: decode(2, 4, 2, 8, 4, 6, 5, pages_per_step=2)},
        {"fn": "paged_attention", "label": "decode/pps1",
         "build": lambda: decode(2, 4, 2, 8, 4, 5, 3)},
        {"fn": "paged_attention", "label": "decode/int8",
         "build": lambda: decode(2, 4, 2, 8, 4, 6, 5, quantized=True,
                                 pages_per_step=2)},
        {"fn": "paged_chunk_attention", "label": "chunk/pps2-ragged",
         "build": lambda: chunk(2, 3, 4, 2, 8, 4, 6, 5, pages_per_step=2,
                                heads_per_step=1)},
        {"fn": "paged_chunk_attention", "label": "chunk/verify-window",
         "build": lambda: chunk(2, 4, 4, 2, 8, 4, 6, 7, S_w=2,
                                pages_per_step=3, heads_per_step=1)},
        # one (1, KH, psize, D) block per page offset: all kv heads in a
        # step, pages divisible and ragged, head groups, derived tiling
        {"fn": "paged_chunk_attention", "label": "chunk/all-heads-pps4",
         "build": lambda: chunk(2, 1, 8, 4, 8, 4, 12, 8, pages_per_step=4,
                                heads_per_step=4)},
        {"fn": "paged_chunk_attention", "label": "chunk/all-heads-ragged",
         "build": lambda: chunk(2, 2, 8, 4, 8, 4, 12, 7, S_w=1,
                                pages_per_step=3, heads_per_step=4)},
        {"fn": "paged_chunk_attention", "label": "chunk/head-pairs-int8",
         "build": lambda: chunk(2, 1, 8, 4, 8, 4, 12, 5, quantized=True,
                                pages_per_step=2, heads_per_step=2)},
        {"fn": "paged_chunk_attention", "label": "chunk/derived",
         "build": lambda: chunk(3, 1, 4, 2, 8, 4, 20, 6)},
    ]


def _flash_entries() -> List[dict]:
    def build(B, H, KH, Sq, Skv, D, **kw):
        a = [FakeArray((B, H, Sq, D)), FakeArray((B, KH, Skv, D)),
             FakeArray((B, KH, Skv, D))]
        return a, {"scale": 1.0, **kw}

    return [
        {"fn": "flash_attention", "label": "flash/causal-gqa",
         "build": lambda: build(2, 4, 2, 24, 40, 8, block_q=8, block_k=16)},
        # non-divisible block sizes: the wrapper's bq //= 2 loop must yield
        # an exactly-covering grid
        {"fn": "flash_attention", "label": "flash/window-ragged-blocks",
         "build": lambda: build(2, 2, 2, 24, 40, 8, block_q=16, block_k=16,
                                causal=False, window=8)},
    ]


def _dropout_entries() -> List[dict]:
    def build(G, M, K, N, **kw):
        return ([FakeArray((G, M, K)), FakeArray((K, N)),
                 FakeArray((G, N // kw.get("block_n", 128)))], kw)

    return [
        {"fn": "dropout_matmul", "label": "dropout/4d-grid",
         "build": lambda: build(3, 16, 32, 64, block_m=8, block_n=32,
                                block_k=16)},
    ]


def _ssd_entries() -> List[dict]:
    def build(B, S, H, P, N, L=0, **kw):
        # L > 0: the engine's pool of L layers' states, one row advanced
        state = FakeArray((L, B, H, P, N) if L else (B, H, P, N))
        args = [FakeArray((B, S, H, P)), FakeArray((B, S, H)),
                FakeArray((H,)), FakeArray((B, S, N)),
                FakeArray((B, S, N)), state, FakeArray((B,)),
                Table("layer", (1,), 0, max(L, 1) - 1)]
        return args, kw

    return [
        {"fn": "ssd_chunk_scan", "label": "ssd/chunked",
         "build": lambda: build(2, 24, 3, 4, 8, chunk=8)},
        {"fn": "ssd_chunk_scan", "label": "ssd/chunk-shrunk",
         "build": lambda: build(2, 24, 3, 4, 8, chunk=7)},
        # 256-row chunks: head groups of 8 of 16 heads, a row of a
        # 5-layer state pool
        {"fn": "ssd_chunk_scan", "label": "ssd/head-groups",
         "build": lambda: build(2, 256, 16, 4, 8, L=5)},
        # the serving decode call: one position per slot
        {"fn": "ssd_chunk_scan", "label": "ssd/decode",
         "build": lambda: build(3, 1, 16, 4, 8, L=5)},
    ]


KERNEL_SPECS: Dict[str, List[dict]] = {
    "src/repro/kernels/paged_attention/kernel.py": _paged_entries(),
    "src/repro/kernels/flash_attention/kernel.py": _flash_entries(),
    "src/repro/kernels/dropout_matmul/kernel.py": _dropout_entries(),
    "src/repro/kernels/ssd/kernel.py": _ssd_entries(),
}

# kernels whose block-table gathers must honor the NULL_PAGE contract
_NULL_PAGE_TABLE = {"paged_attention": "block_tables",
                    "paged_chunk_attention": "block_tables"}


# --------------------------------------------------------------------------
# running entries against a file
# --------------------------------------------------------------------------
def _null_page_contract(env, fn: str,
                        override=None) -> Optional[Tuple[str, int]]:
    if override is not None:
        return tuple(override)
    table = _NULL_PAGE_TABLE.get(fn)
    if table is None:
        return None
    null_page = env.get("NULL_PAGE") if env.has("NULL_PAGE") else 0
    return (table, null_page)


def run_entry(path: str, src: str, entry: dict) -> List[Report]:
    """Interpret ``src``, call ``entry['fn']``, verify every capture."""
    interp, env = interpret_file(src, path)
    fn = entry["fn"]
    if not env.has(fn):
        raise GeometryError(f"{path}: no function {fn!r} at module level")
    if "build" in entry:
        args, kwargs = entry["build"]()
    else:
        args, kwargs = _decode_literal_args(entry)
    interp.call(env.get(fn), tuple(args), kwargs)
    if not interp.captures:
        raise GeometryError(f"{path}: {fn} made no pallas_call")
    contract = _null_page_contract(env, fn, entry.get("null_page"))
    label = entry.get("label", fn)
    reports = []
    for i, cap in enumerate(interp.captures):
        name = label if len(interp.captures) == 1 else f"{label}#{i}"
        geom = geometry_from_capture(cap, name, path, null_page=contract)
        reports.append(verify(geom))
    return reports


def _decode_literal_args(entry: dict):
    def dec(spec):
        if isinstance(spec, dict):
            if "array" in spec:
                return FakeArray(tuple(spec["array"]),
                                 spec.get("dtype", "float32"))
            if "table" in spec:
                lo, hi = spec.get("range", (0, 0))
                return Table(spec["table"], tuple(spec["shape"]), lo, hi)
            raise GeometryError(f"bad HORNSHAPE arg spec {spec!r}")
        return spec

    args = [dec(a) for a in entry.get("args", [])]
    kwargs = {k: dec(v) for k, v in entry.get("kwargs", {}).items()}
    return args, kwargs


def _hornshape_decl(src: str) -> Optional[dict]:
    """The module-level ``HORNSHAPE = {literal}`` declaration, if any."""
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return None
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == "HORNSHAPE":
            return ast.literal_eval(stmt.value)
    return None


def entries_for(path: Path, src: str) -> Optional[List[dict]]:
    decl = _hornshape_decl(src)
    if decl is not None:
        return list(decl.get("entries", []))
    posix = path.as_posix()
    for suffix, entries in KERNEL_SPECS.items():
        if posix.endswith(suffix) or posix.endswith(
                suffix.split("src/repro/")[-1]):
            return entries
    return None


def check_file(path: Path) -> List[Report]:
    src = path.read_text()
    entries = entries_for(path, src)
    if entries is None:
        raise GeometryError(
            f"{path}: no HORNSHAPE declaration and not a registered kernel")
    reports: List[Report] = []
    for entry in entries:
        reports.extend(run_entry(str(path), src, entry))
    return reports


def check_kernels(root: Path = Path(".")) -> List[Tuple[str, Report]]:
    """Verify every registered kernel file under ``root``."""
    out: List[Tuple[str, Report]] = []
    for rel in KERNEL_SPECS:
        p = root / rel
        for rep in check_file(p):
            out.append((rel, rep))
    return out


# --------------------------------------------------------------------------
# runtime twin: cross-check symbolic verdicts at the engine's geometry
# --------------------------------------------------------------------------
def crosscheck_paged_geometry(*, batch: int, kv_heads: int, head_dim: int,
                              page_size: int, num_pages: int,
                              max_pages: int,
                              pages_per_step: Optional[int] = None,
                              quantized: bool = False) -> List[str]:
    """Verify the serving step's paged chunk attention at a *concrete
    serving* geometry — its decode call (chunk width 1), tiled as the
    kernel tiles it (``pages_per_step=None``: derived) — and compare the
    symbolic verdicts against brute-force enumeration.  Returns alert
    strings (empty == proved and consistent) for ``serve.py --sanitize``."""
    rel = "src/repro/kernels/paged_attention/kernel.py"
    path = _find_kernel_source(rel)
    if path is None:
        return [f"hornshape: cannot locate {rel}"]
    H = kv_heads * max(1, 4 // max(kv_heads, 1))  # any multiple of KH works
    entry = {
        "fn": "paged_chunk_attention", "label": "runtime-geometry",
        "build": lambda: (
            [FakeArray((batch, 1, H, head_dim)),
             FakeArray((num_pages, kv_heads, page_size, head_dim),
                       "int8" if quantized else "bfloat16"),
             FakeArray((num_pages, kv_heads, page_size, head_dim),
                       "int8" if quantized else "bfloat16"),
             Table("block_tables", (batch, max_pages), 0, num_pages - 1),
             Table("starts", (batch,), 0, max_pages * page_size),
             Table("chunk_lens", (batch,), 0, 1)],
            dict(scale=1.0, pages_per_step=pages_per_step,
                 **({"k_scale": FakeArray((num_pages, kv_heads)),
                     "v_scale": FakeArray((num_pages, kv_heads))}
                    if quantized else {}))),
    }
    alerts: List[str] = []
    try:
        reports = run_entry(str(path), path.read_text(), entry)
    except (GeometryError, AnalysisError) as e:
        return [f"hornshape: {e}"]
    for rep in reports:
        for f in rep.findings:
            alerts.append(f"hornshape: {f.rule} {f.message}")
        try:
            bf = brute_force(rep.geometry)
        except GeometryError:
            continue
        for k, v in bf.items():
            sv = rep.verdicts.get(k)
            if sv is not None and sv != v:
                alerts.append(
                    f"hornshape-divergence: {k} symbolic={sv!r} "
                    f"brute-force={v!r} at the engine geometry")
    return alerts


def _find_kernel_source(rel: str) -> Optional[Path]:
    for base in (Path.cwd(), Path.cwd().parent,
                 Path(__file__).resolve().parents[3]):
        p = base / rel
        if p.exists():
            return p
        q = base / rel.split("src/")[-1]
        if q.exists():
            return q
    return None


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="hornshape",
        description="symbolic BlockSpec/grid verification for Pallas calls")
    ap.add_argument("paths", nargs="*",
                    help="kernel files (default: the built-in registry)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    results: List[Tuple[str, Report]] = []
    try:
        if args.paths:
            for p in args.paths:
                for rep in check_file(Path(p)):
                    results.append((p, rep))
        else:
            results = check_kernels()
    except (GeometryError, AnalysisError, OSError) as e:
        print(f"hornshape: error: {e}", file=sys.stderr)
        return 2

    n_findings = sum(len(r.findings) for _, r in results)
    if args.as_json:
        doc = {
            "results": [
                {"path": p, "geometry": r.geometry.name,
                 "grid": list(r.geometry.grid),
                 "obligations": len(r.verdicts),
                 "symbolic": r.proved_symbolically(),
                 "findings": [
                     {"rule": f.rule, "path": f.path, "line": f.line,
                      "message": f.message} for f in r.findings]}
                for p, r in results],
            "ok": n_findings == 0,
        }
        print(json.dumps(doc, indent=2))
    else:
        for _, rep in results:
            print("\n".join(rep.render()))
        total = sum(len(r.verdicts) for _, r in results)
        sym = sum(r.proved_symbolically() for _, r in results)
        print(f"hornshape: {len(results)} geometries, {total} obligations "
              f"({sym} symbolic), {n_findings} findings")
    return 1 if n_findings else 0


if __name__ == "__main__":
    sys.exit(main())
