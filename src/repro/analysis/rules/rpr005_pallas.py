"""RPR005 pallas-spec: BlockSpec/grid coherence + static VMEM budget.

Pallas mistakes in this repo fail late (Mosaic compile error on real
TPUs, or silent garbage from a mis-indexed block) because CI runs the
kernels in interpret mode.  Five properties ARE statically checkable
at every ``pl.pallas_call`` site, and this rule checks them:

* **index-map arity** — every BlockSpec's ``lambda`` must take exactly
  one argument per grid axis;
* **out rank** — each out_spec block tuple must have the same rank as
  its paired ``ShapeDtypeStruct`` shape;
* **tile clamping** — a block dim that *varies* with a grid axis (its
  index-map element is a bare grid parameter) must be a clamped local
  (the ``t_ = min(t, max(1, X))`` / ``max(r, (t // r) * r)`` idiom that
  guarantees the padded operand dim divides, DESIGN.md §8) — a raw
  parameter or hardcoded literal tile (other than 1) can stop dividing
  the operand the moment a caller passes a new shape;
* **rank-1 blocks** — the TPU lowering accepts a rank-1 block only if
  it is the whole vector or a multiple of 128 lanes, and XLA lays a
  32-bit vector of 1024+ elements out in 1024-element tiles that the
  block must match.  A rank-1 block that moves along a grid axis is not
  the whole vector, so its tile must resolve to a multiple of 1024 (a
  clamp ``min(t, X)`` with such a ``t`` yields either ``t`` or the
  whole vector); otherwise carry the vector as a 2-D ``(n, 1)`` /
  ``(1, n)`` block;
* **VMEM budget** — a static upper-bound estimate per kernel: all
  resolvable block tiles + ``scratch_shapes`` + (for rank-3 grids) the
  broadcast cube over the distinct tile symbols, the dominant term of
  the minhash-family kernels.  DESIGN.md §8's ~530 KiB budget becomes
  a checked number with a configurable ceiling (``--vmem-limit``,
  default 1 MiB).  Dims resolve through locals, param defaults, and
  module constants; unresolvable dims make the estimate partial, which
  can still *exceed* the ceiling (sound) but never pass a kernel that
  a full resolution would fail.
"""
from __future__ import annotations

import ast

from repro.analysis.findings import Finding
from repro.analysis.rules.base import FileContext, Rule, iter_scopes

_DTYPE_BYTES = {
    "uint32": 4, "int32": 4, "float32": 4, "int64": 8, "float64": 8,
    "uint64": 8, "uint8": 1, "int8": 1, "bool_": 1, "bfloat16": 2,
    "float16": 2, "uint16": 2, "int16": 2,
}


def _is_minmax(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("min", "max"))


class _Resolver:
    """Upper-bound integer resolution through locals/params/constants."""

    def __init__(self, module: ast.Module, fn: ast.FunctionDef):
        self.env: dict[str, int] = {}
        self.clamped: set[str] = set()
        for node in module.body:
            self._learn_assign(node, module_level=True)
        args = fn.args
        pos = args.posonlyargs + args.args
        for a, d in zip(pos[len(pos) - len(args.defaults):],
                        args.defaults):
            v = self.eval(d)
            if v is not None:
                self.env[a.arg] = v
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None:
                v = self.eval(d)
                if v is not None:
                    self.env[a.arg] = v
        for node in ast.walk(fn):
            self._learn_assign(node)

    def _learn_assign(self, node: ast.AST, module_level: bool = False):
        if not isinstance(node, ast.Assign):
            return
        targets, values = [], []
        if len(node.targets) == 1 and isinstance(node.targets[0],
                                                 ast.Tuple):
            tgt = node.targets[0]
            if isinstance(node.value, ast.Tuple) and \
                    len(node.value.elts) == len(tgt.elts):
                targets, values = tgt.elts, node.value.elts
        else:
            targets = [t for t in node.targets]
            values = [node.value] * len(targets)
        for t, v in zip(targets, values):
            if not isinstance(t, ast.Name):
                continue
            if _is_minmax(v):
                self.clamped.add(t.id)
            val = self.eval(v)
            if val is not None:
                self.env[t.id] = val
            elif not module_level:
                self.env.pop(t.id, None)

    def eval(self, node: ast.AST) -> int | None:
        """Upper bound of an int expression; None if unresolvable."""
        if isinstance(node, ast.Constant) and type(node.value) is int:
            return node.value
        if isinstance(node, ast.Name):
            return self.env.get(node.id)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            v = self.eval(node.operand)
            return -v if v is not None else None
        if isinstance(node, ast.BinOp):
            le, ri = self.eval(node.left), self.eval(node.right)
            if le is None or ri is None:
                return None
            if isinstance(node.op, ast.Mult):
                return le * ri
            if isinstance(node.op, ast.Add):
                return le + ri
            if isinstance(node.op, ast.Sub):
                return le - ri
            if isinstance(node.op, ast.FloorDiv) and ri != 0:
                return le // ri
            return None
        if _is_minmax(node):
            vals = [self.eval(a) for a in node.args]
            known = [v for v in vals if v is not None]
            if not known:
                return None
            if node.func.id == "min":
                return min(known)  # min <= every arg: sound upper bound
            # max over a partial set is NOT an upper bound: an
            # unresolved operand usually carries the runtime dim
            # (max(1, L)); downstream min() clamps recover the bound.
            return max(known) if len(known) == len(vals) else None
        return None


class PallasSpec(Rule):
    rule_id = "RPR005"
    name = "pallas-spec"

    def applies(self, ctx: FileContext) -> bool:
        src = "\n".join(ctx.lines)
        return "pallas_call" in src

    def check(self, ctx: FileContext) -> list[Finding]:
        out: list[Finding] = []
        for fn, qual in iter_scopes(ctx.tree):
            calls = [n for n in ast.walk(fn)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Attribute)
                     and n.func.attr == "pallas_call"]
            for call in calls:
                out.extend(self._check_site(ctx, fn, call, qual))
        return out

    # -- one pallas_call site ------------------------------------------------

    def _check_site(self, ctx, fn, call, qual) -> list[Finding]:
        out: list[Finding] = []
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        grid = kw.get("grid")
        grid_rank = (len(grid.elts)
                     if isinstance(grid, ast.Tuple) else None)
        in_specs = self._spec_list(kw.get("in_specs"))
        out_specs = self._spec_list(kw.get("out_specs"))
        out_shapes = self._shape_list(kw.get("out_shape"))
        res = _Resolver(ctx.tree, fn)

        for spec in in_specs + out_specs:
            out.extend(self._check_spec(ctx, spec, grid_rank, res, qual))

        if len(out_specs) == len(out_shapes):
            for spec, shp in zip(out_specs, out_shapes):
                block = self._block_tuple(spec)
                shape = self._sds_shape(shp)
                if block is not None and shape is not None and \
                        len(block.elts) != len(shape.elts):
                    out.append(self.finding(
                        ctx, spec,
                        f"out_spec block rank {len(block.elts)} != "
                        f"out_shape rank {len(shape.elts)}",
                        symbol="out-rank-mismatch", qualname=qual))

        est, partial = self._vmem_estimate(
            in_specs, out_specs, out_shapes, kw.get("scratch_shapes"),
            grid_rank, res)
        if est > ctx.vmem_limit:
            kib = est / 1024
            out.append(self.finding(
                ctx, call,
                f"static VMEM estimate ~{kib:.0f} KiB exceeds the "
                f"{ctx.vmem_limit // 1024} KiB ceiling"
                + (" (partial resolution: true usage is higher)"
                   if partial else "")
                + "; shrink the tile dims or raise --vmem-limit with a "
                  "DESIGN.md §8 budget note",
                symbol="vmem-budget", qualname=qual))
        return out

    def _check_spec(self, ctx, spec, grid_rank, res, qual):
        out = []
        block = self._block_tuple(spec)
        lam = self._index_map(spec)
        if lam is not None and grid_rank is not None:
            arity = len(lam.args.posonlyargs + lam.args.args)
            if arity != grid_rank:
                out.append(self.finding(
                    ctx, spec,
                    f"BlockSpec index map takes {arity} args but the "
                    f"grid has {grid_rank} axes",
                    symbol="index-map-arity", qualname=qual))
        if block is None or lam is None or \
                not isinstance(lam.body, ast.Tuple):
            return out
        params = {a.arg for a in (lam.args.posonlyargs + lam.args.args)}
        if len(block.elts) == 1 and len(lam.body.elts) == 1:
            dim, idx = block.elts[0], lam.body.elts[0]
            tile = res.eval(dim)
            moves = any(isinstance(n, ast.Name) and n.id in params
                        for n in ast.walk(idx))
            if moves and (tile is None or tile % 1024 != 0):
                out.append(self.finding(
                    ctx, dim if hasattr(dim, "lineno") else spec,
                    f"rank-1 block ({ast.unparse(dim)},) is tiled along "
                    "a grid axis: the TPU lowering needs a rank-1 block "
                    "to be the whole vector or a multiple of 128 lanes "
                    "(1024 to match XLA's layout of a long vector) — "
                    "carry the vector as a 2-D (n, 1) or (1, n) block",
                    symbol=f"rank1-block:{ast.unparse(dim)}",
                    qualname=qual))
        for i, (dim, idx) in enumerate(zip(block.elts, lam.body.elts)):
            varies = isinstance(idx, ast.Name) and idx.id in params
            if not varies:
                continue
            if isinstance(dim, ast.Constant) and dim.value == 1:
                continue  # block of 1 divides everything
            if isinstance(dim, ast.Name) and dim.id in res.clamped:
                continue
            if isinstance(dim, ast.BinOp):
                # derived dims (tm_ // r): require the base clamped
                names = [n.id for n in ast.walk(dim)
                         if isinstance(n, ast.Name)]
                if any(n in res.clamped for n in names):
                    continue
            out.append(self.finding(
                ctx, dim if hasattr(dim, "lineno") else spec,
                f"tile dim {ast.unparse(dim)!r} varies with a grid axis "
                "but is not clamped to the operand bounds (use the "
                "`t_ = min(t, max(1, X))` / ceil-pad idiom, DESIGN.md "
                "§8) — an unpadded operand dim it does not divide "
                "mis-tiles the kernel",
                symbol=f"unclamped-dim:{ast.unparse(dim)}",
                qualname=qual))
        return out

    # -- VMEM estimate -------------------------------------------------------

    def _vmem_estimate(self, in_specs, out_specs, out_shapes, scratch,
                       grid_rank, res) -> tuple[int, bool]:
        total, partial = 0, False
        dtype_by_spec = {}
        if len(out_specs) == len(out_shapes):
            for spec, shp in zip(out_specs, out_shapes):
                dtype_by_spec[id(spec)] = self._sds_dtype_bytes(shp)
        # Per grid axis, the widest tile extent indexed along it: their
        # product bounds the broadcast cube a rank-3 kernel can build
        # (the (TD, TL, TM) seeded-hash intermediate of the minhash
        # family, DESIGN.md §8 — the dominant VMEM term).
        axis_extent: dict[str, int] = {}
        axis_unresolved = False
        for spec in in_specs + out_specs:
            block = self._block_tuple(spec)
            if block is None:
                continue
            nbytes = dtype_by_spec.get(id(spec), 4)
            size = 1
            ok = True
            lam = self._index_map(spec)
            idx_elts = (lam.body.elts
                        if lam is not None
                        and isinstance(lam.body, ast.Tuple)
                        else [])
            params = ({a.arg for a in (lam.args.posonlyargs
                                       + lam.args.args)}
                      if lam is not None else set())
            for i, dim in enumerate(block.elts):
                v = res.eval(dim)
                if v is None:
                    ok = False
                else:
                    size *= v
                if i < len(idx_elts) and isinstance(
                        idx_elts[i], ast.Name) and \
                        idx_elts[i].id in params:
                    if v is None:
                        axis_unresolved = True
                    else:
                        axis_extent[idx_elts[i].id] = max(
                            axis_extent.get(idx_elts[i].id, 1), v)
            if ok:
                total += size * nbytes
            else:
                partial = True
        if isinstance(scratch, (ast.List, ast.Tuple)):
            for s in scratch.elts:
                v = self._scratch_bytes(s, res)
                if v is None:
                    partial = True
                else:
                    total += v
        if grid_rank is not None and grid_rank >= 3 and axis_extent:
            if axis_unresolved:
                partial = True
            else:
                cube = 1
                for v in axis_extent.values():
                    cube *= v
                total += cube * 4
        return total, partial

    def _scratch_bytes(self, node, res) -> int | None:
        if not (isinstance(node, ast.Call) and node.args):
            return None
        shape = node.args[0]
        if not isinstance(shape, ast.Tuple):
            return None
        size = 1
        for dim in shape.elts:
            v = res.eval(dim)
            if v is None:
                return None
            size *= v
        nbytes = 4
        if len(node.args) > 1 and isinstance(node.args[1], ast.Attribute):
            nbytes = _DTYPE_BYTES.get(node.args[1].attr, 4)
        return size * nbytes

    # -- AST plumbing --------------------------------------------------------

    @staticmethod
    def _spec_list(node) -> list[ast.Call]:
        if node is None:
            return []
        items = node.elts if isinstance(node, (ast.List, ast.Tuple)) \
            else [node]
        return [n for n in items
                if isinstance(n, ast.Call)
                and ((isinstance(n.func, ast.Attribute)
                      and n.func.attr == "BlockSpec")
                     or (isinstance(n.func, ast.Name)
                         and n.func.id == "BlockSpec"))]

    @staticmethod
    def _shape_list(node) -> list[ast.Call]:
        if node is None:
            return []
        items = node.elts if isinstance(node, (ast.List, ast.Tuple)) \
            else [node]
        return [n for n in items if isinstance(n, ast.Call)]

    @staticmethod
    def _block_tuple(spec: ast.Call) -> ast.Tuple | None:
        if spec.args and isinstance(spec.args[0], ast.Tuple):
            return spec.args[0]
        for k in spec.keywords:
            if k.arg == "block_shape" and isinstance(k.value, ast.Tuple):
                return k.value
        return None

    @staticmethod
    def _index_map(spec: ast.Call) -> ast.Lambda | None:
        if len(spec.args) > 1 and isinstance(spec.args[1], ast.Lambda):
            return spec.args[1]
        for k in spec.keywords:
            if k.arg == "index_map" and isinstance(k.value, ast.Lambda):
                return k.value
        return None

    @staticmethod
    def _sds_shape(sds: ast.Call) -> ast.Tuple | None:
        if sds.args and isinstance(sds.args[0], ast.Tuple):
            return sds.args[0]
        for k in sds.keywords:
            if k.arg == "shape" and isinstance(k.value, ast.Tuple):
                return k.value
        return None

    def _sds_dtype_bytes(self, sds: ast.Call) -> int:
        node = None
        if len(sds.args) > 1:
            node = sds.args[1]
        for k in sds.keywords:
            if k.arg == "dtype":
                node = k.value
        if isinstance(node, ast.Attribute):
            return _DTYPE_BYTES.get(node.attr, 4)
        return 4
