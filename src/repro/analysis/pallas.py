"""pallas-consistency: static shape agreement for ``pl.pallas_call`` sites.

The halo-tiled kernels in ``kernels/spiking_conv.py`` and
``kernels/spiking_conv_lif.py`` encode three contracts that TPU lowering
only reports asynchronously (or worse, mis-tiles silently when padding
drifts):

1. every BlockSpec index-map lambda takes exactly ``len(grid)`` args;
2. every BlockSpec block-shape rank equals the index-map's returned
   tuple arity (block coordinates are per-dimension);
3. statically-provable block dims divide the (padded) array dims they
   tile — ``block_rows`` must divide ``e_h_pad`` etc.;
4. a block shape that uses ``pl.Element`` (element-offset indexing, the
   halo reads) uses it on every non-squeezed dim — Mosaic refuses a mix
   ("All block dimensions must be Elements or none of them").

Block dims it knows: ``pl.Element(n)`` (an element-offset window: the
index map returns element offsets, so divisibility does not apply),
``pl.squeezed`` / ``pl.Squeezed()`` / ``None`` (a size-1 dim the kernel
does not see), and plain sizes.  A spec without a block shape — e.g. a
whole-array ``pl.BlockSpec(memory_space=pltpu.SMEM)`` skip table — has
nothing to check but still counts as an operand.

The checker resolves names through simple same-function assignments
(``seq_spec = pl.BlockSpec(...)`` then ``in_specs=[seq_spec, ...]``,
including ``out_specs.append(...)``, the ``[base] + extra`` list
concatenation the chunk-capable fused kernel uses, and an
``[x] if flag else []`` conditional — resolved to its non-empty branch so
the maximal operand set is checked) and only *flags* what it can
*prove* wrong: two integer literals that don't divide, or mismatched
ranks/arities.  Symbolic dims it can't decide pass silently — except the
two idioms the kernels actually use, which it proves correct:
``pad = n_blocks * block_rows`` (block is a literal factor) and
``blk = Dim // groups`` (block is an exact floor-div of the dim).
An extra operand-count check catches the classic "added an input,
forgot its spec" drift.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Tuple

from repro.analysis.base import Finding, Rule, SourceFile

__all__ = ["PallasConsistencyRule"]

_MAX_RESOLVE_DEPTH = 8


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - defensive
        return "<expr>"


class _FuncEnv:
    """Name -> value-expression environment for one function body, plus
    the ``<name>.append(x)`` calls that extend list-valued names."""

    def __init__(self, fn: ast.AST):
        self.assigns: Dict[str, ast.expr] = {}
        self.appends: Dict[str, List[ast.expr]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    self.assigns[t.id] = node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if isinstance(node.target, ast.Name):
                    self.assigns[node.target.id] = node.value
            elif (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "append"
                    and isinstance(node.func.value, ast.Name)
                    and len(node.args) == 1):
                self.appends.setdefault(node.func.value.id,
                                        []).append(node.args[0])

    def resolve(self, node: Optional[ast.expr],
                depth: int = 0) -> Optional[ast.expr]:
        while (isinstance(node, ast.Name) and node.id in self.assigns
               and depth < _MAX_RESOLVE_DEPTH):
            node = self.assigns[node.id]
            depth += 1
        return node

    def as_list(self, node: Optional[ast.expr],
                depth: int = 0) -> Optional[List[ast.expr]]:
        """Resolve a spec/shape argument to its element expressions:
        list/tuple literals, appends to a named list, ``a + b``
        concatenation of resolvable lists, and the ``[x] if flag else []``
        conditional (resolved to its non-empty branch, so the checker sees
        the maximal operand set)."""
        if node is None or depth > _MAX_RESOLVE_DEPTH:
            return None
        appended: List[ast.expr] = []
        if isinstance(node, ast.Name):
            appended = self.appends.get(node.id, [])
        resolved = self.resolve(node)
        if isinstance(resolved, (ast.List, ast.Tuple)):
            return list(resolved.elts) + appended
        if (isinstance(resolved, ast.BinOp)
                and isinstance(resolved.op, ast.Add)):
            left = self.as_list(resolved.left, depth + 1)
            right = self.as_list(resolved.right, depth + 1)
            if left is not None and right is not None:
                return left + right + appended
            return None
        if isinstance(resolved, ast.IfExp):
            body = self.as_list(resolved.body, depth + 1)
            orelse = self.as_list(resolved.orelse, depth + 1)
            if body is not None and orelse is not None \
                    and (not body or not orelse):
                return (body or orelse) + appended
            return None
        return None


def _keyword(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _is_call_to(node: Optional[ast.expr], attr: str) -> bool:
    return (isinstance(node, ast.Call)
            and ((isinstance(node.func, ast.Attribute)
                  and node.func.attr == attr)
                 or (isinstance(node.func, ast.Name)
                     and node.func.id == attr)))


def _is_squeezed(node: ast.expr) -> bool:
    """``pl.squeezed``, ``pl.Squeezed()`` or ``None`` as a block dim."""
    if isinstance(node, ast.Constant) and node.value is None:
        return True
    if isinstance(node, ast.Attribute) and node.attr == "squeezed":
        return True
    return _is_call_to(node, "Squeezed")


def _lambda_info(node: Optional[ast.expr]) -> Optional[Tuple[int, int]]:
    """(param arity, returned tuple arity) of an index-map lambda."""
    if not isinstance(node, ast.Lambda):
        return None
    params = len(node.args.args)
    body = node.body
    ret = len(body.elts) if isinstance(body, ast.Tuple) else 1
    return params, ret


def _divides(block: Optional[ast.expr], dim: Optional[ast.expr],
             env: _FuncEnv) -> Optional[bool]:
    """Tri-state: True/False when provable, None when unknown."""
    block = env.resolve(block)
    dim = env.resolve(dim)
    if block is None or dim is None:
        return None
    if _is_squeezed(block) or (isinstance(block, ast.Constant)
                               and block.value == 1):
        return True
    if (isinstance(block, ast.Constant) and isinstance(dim, ast.Constant)
            and isinstance(block.value, int) and isinstance(dim.value, int)):
        return block.value != 0 and dim.value % block.value == 0
    b_src, d_src = _unparse(block), _unparse(dim)
    if b_src == d_src:
        return True
    # dim == <...> * block  (e.g. e_h_pad = n_blocks * block_rows)
    if isinstance(dim, ast.BinOp) and isinstance(dim.op, ast.Mult):
        for factor in (dim.left, dim.right):
            f = env.resolve(factor)
            if f is not None and _unparse(f) == b_src:
                return True
            if _unparse(factor) == b_src:
                return True
    # block == dim // k  (e.g. cout_blk = Cout // num_groups; exactness is
    # asserted at runtime by the kernel wrappers)
    if isinstance(block, ast.BinOp) and isinstance(block.op, ast.FloorDiv):
        num = env.resolve(block.left)
        if _unparse(block.left) == d_src or (
                num is not None and _unparse(num) == d_src):
            return True
    return None


class PallasConsistencyRule(Rule):
    name = "pallas-consistency"
    description = ("check pl.pallas_call BlockSpecs: index-map arity vs "
                   "grid rank, block-shape rank vs index-map return arity, "
                   "provable block-dim divisibility, operand/spec counts")

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        pallas_aliases = {
            a.asname or a.name.rsplit(".", 1)[-1]
            for node in ast.walk(sf.tree)
            if isinstance(node, ast.ImportFrom)
            for a in node.names
            if (node.module or "").endswith("pallas") or a.name == "pallas"
        }
        if not pallas_aliases:
            return
        # parent map to find the outer Call that feeds operands into the
        # callable returned by pl.pallas_call(...)
        parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(sf.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        funcs = [n for n in ast.walk(sf.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in funcs:
            env = _FuncEnv(fn)
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id in pallas_aliases):
                    yield from self._check_call(sf, env, node, parents)

    def _check_call(self, sf: SourceFile, env: _FuncEnv, call: ast.Call,
                    parents: Dict[ast.AST, ast.AST]) -> Iterator[Finding]:
        grid = env.resolve(_keyword(call, "grid"))
        grid_rank: Optional[int] = None
        if isinstance(grid, (ast.Tuple, ast.List)):
            grid_rank = len(grid.elts)
        elif grid is not None:
            grid_rank = 1

        in_specs = env.as_list(_keyword(call, "in_specs"))
        out_arg = _keyword(call, "out_specs")
        out_specs = env.as_list(out_arg)
        if out_specs is None and out_arg is not None:
            resolved = env.resolve(out_arg)
            if _is_call_to(resolved, "BlockSpec"):
                out_specs = [out_arg]
        out_shapes = env.as_list(_keyword(call, "out_shape"))
        if out_shapes is None:
            shape_arg = env.resolve(_keyword(call, "out_shape"))
            if _is_call_to(shape_arg, "ShapeDtypeStruct"):
                out_shapes = [shape_arg]

        all_specs: List[Tuple[str, ast.expr]] = []
        for i, s in enumerate(in_specs or []):
            all_specs.append((f"in_specs[{i}]", s))
        for i, s in enumerate(out_specs or []):
            all_specs.append((f"out_specs[{i}]", s))

        spec_ranks: Dict[str, Optional[List[ast.expr]]] = {}
        for label, spec_expr in all_specs:
            spec = env.resolve(spec_expr)
            if not _is_call_to(spec, "BlockSpec"):
                spec_ranks[label] = None
                continue
            assert isinstance(spec, ast.Call)
            block = env.resolve(spec.args[0]) if spec.args else None
            index_map = spec.args[1] if len(spec.args) > 1 else None
            block_dims: Optional[List[ast.expr]] = None
            if isinstance(block, (ast.Tuple, ast.List)):
                block_dims = list(block.elts)
                dims = [env.resolve(d) for d in block_dims]
                kinds = {_is_call_to(d, "Element") for d in dims
                         if d is not None and not _is_squeezed(d)}
                if kinds == {True, False}:
                    yield sf.finding(
                        self.name, spec,
                        f"{label}: block shape mixes pl.Element and plain "
                        f"dims (Mosaic needs Element on every dim or none)")
            spec_ranks[label] = block_dims
            lam = _lambda_info(env.resolve(index_map))
            if lam is not None:
                params, ret = lam
                if grid_rank is not None and params != grid_rank:
                    yield sf.finding(
                        self.name, spec,
                        f"{label}: index-map lambda takes {params} args "
                        f"but grid has rank {grid_rank}")
                if block_dims is not None and ret != len(block_dims):
                    yield sf.finding(
                        self.name, spec,
                        f"{label}: block shape has rank {len(block_dims)} "
                        f"but index map returns {ret} coordinates")

        # pair out_specs with out_shape entries: rank + divisibility
        if out_specs is not None and out_shapes is not None \
                and len(out_specs) == len(out_shapes):
            for i, (spec_expr, shape_expr) in enumerate(
                    zip(out_specs, out_shapes)):
                spec = env.resolve(spec_expr)
                shape_call = env.resolve(shape_expr)
                if not (_is_call_to(spec, "BlockSpec")
                        and _is_call_to(shape_call, "ShapeDtypeStruct")):
                    continue
                assert isinstance(spec, ast.Call)
                assert isinstance(shape_call, ast.Call)
                block = env.resolve(spec.args[0]) if spec.args else None
                if isinstance(block, (ast.Tuple, ast.List)) and any(
                        _is_call_to(env.resolve(d), "Element")
                        for d in block.elts):
                    continue  # element-offset windows, not blocks
                shape = env.resolve(shape_call.args[0]) \
                    if shape_call.args else None
                if not (isinstance(block, (ast.Tuple, ast.List))
                        and isinstance(shape, (ast.Tuple, ast.List))):
                    continue
                if len(block.elts) != len(shape.elts):
                    yield sf.finding(
                        self.name, spec,
                        f"out_specs[{i}]: block shape rank "
                        f"{len(block.elts)} != out_shape rank "
                        f"{len(shape.elts)}")
                    continue
                for d, (b, s) in enumerate(zip(block.elts, shape.elts)):
                    if _divides(b, s, env) is False:
                        yield sf.finding(
                            self.name, spec,
                            f"out_specs[{i}] dim {d}: block dim "
                            f"{_unparse(b)} does not divide array dim "
                            f"{_unparse(s)}")

        # operand count: the pallas_call result is invoked immediately
        outer = parents.get(call)
        if (isinstance(outer, ast.Call) and outer.func is call
                and in_specs is not None
                and not any(isinstance(a, ast.Starred) for a in outer.args)):
            if len(outer.args) != len(in_specs):
                yield sf.finding(
                    self.name, outer,
                    f"pallas_call invoked with {len(outer.args)} operands "
                    f"but in_specs declares {len(in_specs)}")
