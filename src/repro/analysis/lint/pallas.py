"""R4 — Pallas kernel static validator; R6 — VMEM budget abstract
interpreter.

Pallas misconfigurations (grid/index-map arity drift, kernel signature
vs spec-count mismatch, block shapes that don't divide the padded dims)
surface as opaque lowering errors — and only on a TPU.  R4 re-derives
the structural contract of every ``pl.pallas_call`` from the AST alone,
so kernels are validated on any machine, at review time:

  C1  each BlockSpec index-map's arity == len(grid) + num_scalar_prefetch
  C2  an index-map returning a tuple has one coordinate per block dim
  C3  kernel positional params == num_scalar_prefetch + len(in_specs)
      + n_outputs + len(scratch_shapes)
  C4  constant block dims divide the matching constant out_shape dims
  C5  scratch_shapes entries are constructor calls (pltpu.VMEM/SMEM)

R6 goes one layer deeper: it abstractly evaluates every block shape
(through local assignments, keyword defaults, module constants, a
one-level lambda beta-reduction for spec helpers, and configured
worst-case dims for shape-derived symbols like ``hd``/``ps``/``group``)
and totals the kernel's per-invocation VMEM footprint::

  footprint = 2 x sum(in/out block bytes)   # double-buffered pipeline
            + sum(scratch bytes)            # resident accumulators

checked against the budget in ``repro-lint.toml`` (default ~16 MiB per
TensorCore).  Computed footprints are appended to the report notes, so
``make lint`` prints what each kernel actually costs.

Checks degrade gracefully: anything symbolic beyond the evaluator's
reach is skipped (with a note, for R6), never guessed at.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Tuple

from .config import LintConfig
from .engine import Finding, Module, Rule

# (block-shape expr | None, index-map lambda | None, local env for
# evaluating names inside the block expr — carries beta-reduction
# bindings when the spec came from a helper lambda)
_Spec = Optional[Tuple[Optional[ast.expr], Optional[ast.Lambda],
                       Dict[str, ast.expr]]]


class _CallSite:
    """Everything statically extractable from one ``pl.pallas_call``."""

    def __init__(self) -> None:
        self.grid_len: Optional[int] = None
        self.prefetch: int = 0
        self.in_specs: List[_Spec] = []
        self.out_specs: List[_Spec] = []
        self.n_outputs: Optional[int] = None
        self.out_shape_dims: Optional[List[ast.expr]] = None
        self.scratch: Optional[List[ast.expr]] = None
        self.kernel_params: Optional[int] = None
        self.kernel_name: str = "<kernel>"


def _const_int(node: ast.AST) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}


class PallasKernelRule(Rule):
    id = "R4"
    name = "pallas-static-validator"
    hint = ("re-derive the pallas_call contract: index maps take one arg "
            "per grid axis (+ scalar prefetch), return one coordinate per "
            "block dim; the kernel takes prefetch + inputs + outputs + "
            "scratch refs in that order; block dims must divide the "
            "padded array dims")

    # ---- local-name resolution inside the enclosing function -------------

    def _local_env(self, call: ast.Call) -> Dict[str, ast.expr]:
        env: Dict[str, ast.expr] = {}
        fn = call
        while fn is not None and not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
            fn = getattr(fn, "_parent", None)
        if fn is None:
            return env
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                env[node.targets[0].id] = node.value
        return env

    def _deref(self, expr: ast.expr, env: Dict[str, ast.expr],
               depth: int = 4) -> ast.expr:
        while depth > 0 and isinstance(expr, ast.Name) and expr.id in env:
            nxt = env[expr.id]
            if nxt is expr:
                break
            expr, depth = nxt, depth - 1
        return expr

    # ---- extractors -------------------------------------------------------

    def _is_call_to(self, module: Module, expr: ast.AST, leaf: str) -> bool:
        if not isinstance(expr, ast.Call):
            return False
        dotted = module.resolve(expr.func)
        return bool(dotted) and dotted.split(".")[-1] == leaf

    def _block_spec(self, module: Module, expr: ast.expr,
                    env: Dict[str, ast.expr]) -> _Spec:
        """-> (block-shape expr, index-map lambda, eval env); None for
        specs we can't statically resolve."""
        expr = self._deref(expr, env)
        # one-level beta reduction: seg_spec(block_q, True) where
        # seg_spec is a locally-bound lambda returning a BlockSpec
        if isinstance(expr, ast.Call) \
                and not self._is_call_to(module, expr, "BlockSpec"):
            fn = expr.func if isinstance(expr.func, ast.Lambda) \
                else self._deref(expr.func, env)
            if isinstance(fn, ast.Lambda) and not expr.keywords:
                params = [a.arg for a in fn.args.args]
                if len(params) == len(expr.args):
                    inner = dict(env)
                    inner.update(dict(zip(params, expr.args)))
                    return self._block_spec(module, fn.body, inner)
        if not self._is_call_to(module, expr, "BlockSpec"):
            return None
        block: Optional[ast.expr] = None
        imap: Optional[ast.Lambda] = None
        args = list(expr.args)
        for kw in expr.keywords:
            if kw.arg == "block_shape":
                block = kw.value
            elif kw.arg == "index_map":
                imap = kw.value if isinstance(kw.value, ast.Lambda) else imap
        if args:
            block = block or args[0]
        if len(args) > 1 and isinstance(args[1], ast.Lambda):
            imap = imap or args[1]
        return (block, imap, env)

    def _spec_list(self, module: Module, expr: Optional[ast.expr],
                   env: Dict[str, ast.expr]) -> List[_Spec]:
        if expr is None:
            return []
        expr = self._deref(expr, env)
        items = expr.elts if isinstance(expr, (ast.List, ast.Tuple)) else [expr]
        return [self._block_spec(module, e, env) for e in items]

    def _kernel_params(self, module: Module, expr: ast.expr,
                       env: Dict[str, ast.expr]) -> Tuple[Optional[int], str]:
        """-> (positional-param count after partial binding, display name)."""
        expr = self._deref(expr, env)
        bound = 0
        while self._is_call_to(module, expr, "partial") and expr.args:
            bound += len(expr.args) - 1  # extra positional args pre-bind
            expr = self._deref(expr.args[0], env)
        name = None
        if isinstance(expr, ast.Name):
            name = expr.id
            for node in ast.walk(module.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and node.name == name:
                    n = len(node.args.posonlyargs) + len(node.args.args)
                    return max(0, n - bound), name
        return None, name or "<kernel>"

    def _extract(self, module: Module, call: ast.Call) -> _CallSite:
        site = _CallSite()
        env = self._local_env(call)
        kw = {k.arg: k.value for k in call.keywords if k.arg}

        grid = kw.get("grid")
        in_specs = kw.get("in_specs")
        out_specs = kw.get("out_specs")

        spec = kw.get("grid_spec")
        if spec is not None:
            spec = self._deref(spec, env)
            if isinstance(spec, ast.Call):
                skw = {k.arg: k.value for k in spec.keywords if k.arg}
                grid = skw.get("grid", grid)
                in_specs = skw.get("in_specs", in_specs)
                out_specs = skw.get("out_specs", out_specs)
                npf = skw.get("num_scalar_prefetch")
                if npf is not None:
                    site.prefetch = _const_int(self._deref(npf, env)) or 0
                if "scratch_shapes" in skw:
                    kw.setdefault("scratch_shapes", skw["scratch_shapes"])

        if grid is not None:
            grid = self._deref(grid, env)
            if isinstance(grid, ast.Tuple):
                site.grid_len = len(grid.elts)

        site.in_specs = self._spec_list(module, in_specs, env)
        site.out_specs = self._spec_list(module, out_specs, env)

        out_shape = kw.get("out_shape")
        if out_shape is not None:
            out_shape = self._deref(out_shape, env)
            if isinstance(out_shape, (ast.List, ast.Tuple)):
                site.n_outputs = len(out_shape.elts)
                shapes = out_shape.elts
            else:
                site.n_outputs = 1
                shapes = [out_shape]
            if len(shapes) == 1 and self._is_call_to(
                    module, shapes[0], "ShapeDtypeStruct"):
                sd = shapes[0].args[0] if shapes[0].args else None
                for skw in shapes[0].keywords:
                    if skw.arg == "shape":
                        sd = skw.value
                sd = self._deref(sd, env) if sd is not None else None
                if isinstance(sd, ast.Tuple):
                    site.out_shape_dims = list(sd.elts)

        scratch = kw.get("scratch_shapes")
        if scratch is not None:
            scratch = self._deref(scratch, env)
            if isinstance(scratch, (ast.List, ast.Tuple)):
                site.scratch = list(scratch.elts)

        if call.args:
            site.kernel_params, site.kernel_name = self._kernel_params(
                module, call.args[0], env)
        return site

    def _sites(self, module: Module) -> List[Tuple[ast.Call, _CallSite]]:
        out = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = module.resolve(node.func)
            if not dotted or dotted.split(".")[-1] != "pallas_call":
                continue
            out.append((node, self._extract(module, node)))
        return out

    # ---- checks -----------------------------------------------------------

    def _check_site(self, module: Module, call: ast.Call,
                    site: _CallSite) -> Iterable[Finding]:
        out: List[Finding] = []
        want_arity = (site.grid_len + site.prefetch
                      if site.grid_len is not None else None)

        for kind, specs in (("in", site.in_specs), ("out", site.out_specs)):
            for i, spec in enumerate(specs):
                if spec is None:
                    continue
                block, imap, _ = spec
                if imap is not None and want_arity is not None:
                    arity = len(imap.args.posonlyargs) + len(imap.args.args)
                    if arity != want_arity:
                        out.append(self.finding(
                            module, imap,
                            f"{kind}_specs[{i}] index map takes {arity} "
                            f"args but grid+prefetch needs {want_arity}"))
                if imap is not None and isinstance(block, ast.Tuple) \
                        and isinstance(imap.body, ast.Tuple) \
                        and len(imap.body.elts) != len(block.elts):
                    out.append(self.finding(
                        module, imap,
                        f"{kind}_specs[{i}] index map returns "
                        f"{len(imap.body.elts)} coordinates for a "
                        f"{len(block.elts)}-dim block"))

        if site.kernel_params is not None and site.n_outputs is not None \
                and site.scratch is not None:
            want = (site.prefetch + len(site.in_specs) + site.n_outputs
                    + len(site.scratch))
            if site.kernel_params != want:
                out.append(self.finding(
                    module, call,
                    f"kernel {site.kernel_name} takes {site.kernel_params} "
                    f"refs but specs provide {want} (= {site.prefetch} "
                    f"prefetch + {len(site.in_specs)} in + "
                    f"{site.n_outputs} out + {len(site.scratch)} scratch)"))

        if site.out_shape_dims is not None and len(site.out_specs) == 1 \
                and site.out_specs[0] is not None:
            block, _, _ = site.out_specs[0]
            if isinstance(block, ast.Tuple) \
                    and len(block.elts) == len(site.out_shape_dims):
                for d, (b_e, s_e) in enumerate(
                        zip(block.elts, site.out_shape_dims)):
                    b, s = _const_int(b_e), _const_int(s_e)
                    if b is not None and s is not None and b > 0 \
                            and s % b != 0:
                        out.append(self.finding(
                            module, b_e,
                            f"out block dim {d} is {b} which does not "
                            f"divide the padded array dim {s}"))

        if site.scratch is not None:
            for i, entry in enumerate(site.scratch):
                if not isinstance(entry, ast.Call):
                    out.append(self.finding(
                        module, entry,
                        f"scratch_shapes[{i}] is not a pltpu.VMEM/SMEM "
                        "constructor call"))
        return out

    def check(self, module: Module) -> Iterable[Finding]:
        out: List[Finding] = []
        for call, site in self._sites(module):
            out.extend(self._check_site(module, call, site))
        return out


class VmemBudgetRule(PallasKernelRule):
    id = "R6"
    name = "pallas-vmem-budget"
    hint = ("shrink block_q/block_k (or the page size) until "
            "2 x sum(block bytes) + scratch fits the per-core VMEM "
            "budget in repro-lint.toml — an over-budget kernel fails to "
            "lower (or silently spills) on real hardware")

    def __init__(self, config: Optional[LintConfig] = None):
        self.config = config or LintConfig()

    # ---- abstract dim evaluator ------------------------------------------

    def _module_consts(self, module: Module) -> Dict[str, int]:
        cached = getattr(module, "_int_consts", None)
        if cached is not None:
            return cached
        out: Dict[str, int] = {}
        for node in module.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                v = _const_int(node.value)
                if v is not None:
                    out[node.targets[0].id] = v
        module._int_consts = out  # type: ignore[attr-defined]
        return out

    def _fn_defaults(self, call: ast.Call) -> Dict[str, ast.expr]:
        """keyword/positional defaults of the function enclosing the
        pallas_call — where block_q=128-style tile knobs live."""
        fn = call
        while fn is not None and not isinstance(
                fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn = getattr(fn, "_parent", None)
        if fn is None:
            return {}
        out: Dict[str, ast.expr] = {}
        a = fn.args
        for arg, default in zip(a.args[len(a.args) - len(a.defaults):],
                                a.defaults):
            out[arg.arg] = default
        for arg, default in zip(a.kwonlyargs, a.kw_defaults):
            if default is not None:
                out[arg.arg] = default
        return out

    def _eval_dim(self, expr: ast.expr, env: Dict[str, ast.expr],
                  defaults: Dict[str, ast.expr], consts: Dict[str, int],
                  depth: int = 6) -> Optional[int]:
        if depth <= 0:
            return None
        if isinstance(expr, ast.Constant) and expr.value is None:
            return 1        # squeezed block dim
        v = _const_int(expr)
        if v is not None:
            return v
        if isinstance(expr, ast.Name):
            nm = expr.id
            if nm in env and env[nm] is not expr:
                sub = dict(env)
                del sub[nm]      # no self-recursion through reassignment
                v = self._eval_dim(env[nm], sub, defaults, consts, depth - 1)
                if v is not None:
                    return v
            if nm in defaults:
                v = self._eval_dim(defaults[nm], {}, {}, consts, depth - 1)
                if v is not None:
                    return v
            if nm in consts:
                return consts[nm]
            return self.config.dims.get(nm)
        if isinstance(expr, ast.BinOp):
            lhs = self._eval_dim(expr.left, env, defaults, consts, depth - 1)
            rhs = self._eval_dim(expr.right, env, defaults, consts, depth - 1)
            if lhs is None or rhs is None:
                return None
            if isinstance(expr.op, ast.Add):
                return lhs + rhs
            if isinstance(expr.op, ast.Sub):
                return lhs - rhs
            if isinstance(expr.op, ast.Mult):
                return lhs * rhs
            if isinstance(expr.op, (ast.FloorDiv, ast.Div)) and rhs:
                return lhs // rhs
        return None

    def _dtype_bytes(self, module: Module, expr: Optional[ast.AST]) -> int:
        if expr is not None:
            dotted = module.resolve(expr) or ""
            leaf = dotted.split(".")[-1]
            if leaf in _DTYPE_BYTES:
                return _DTYPE_BYTES[leaf]
        return self.config.assumed_input_bytes

    def _block_bytes(self, module: Module, spec: _Spec,
                     defaults: Dict[str, ast.expr],
                     consts: Dict[str, int]) -> Optional[int]:
        if spec is None:
            return None
        block, _, env = spec
        if block is None:
            return None
        block = self._deref(block, env)
        if not isinstance(block, ast.Tuple):
            return None
        total = self.config.assumed_input_bytes
        for e in block.elts:
            d = self._eval_dim(e, env, defaults, consts)
            if d is None:
                return None
            total *= d
        return total

    def _scratch_bytes(self, module: Module, entry: ast.expr,
                       env: Dict[str, ast.expr],
                       defaults: Dict[str, ast.expr],
                       consts: Dict[str, int]) -> Optional[int]:
        if not isinstance(entry, ast.Call) or not entry.args:
            return None
        shape = self._deref(entry.args[0], env)
        if not isinstance(shape, ast.Tuple):
            return None
        dtype = entry.args[1] if len(entry.args) > 1 else None
        total = self._dtype_bytes(module, dtype)
        for e in shape.elts:
            d = self._eval_dim(e, env, defaults, consts)
            if d is None:
                return None
            total *= d
        return total

    # ---- the budget check -------------------------------------------------

    def check(self, module: Module) -> Iterable[Finding]:
        out: List[Finding] = []
        consts = self._module_consts(module)
        budget = self.config.vmem_budget_bytes
        for call, site in self._sites(module):
            where = f"{module.path}:{call.lineno}"
            defaults = self._fn_defaults(call)
            env = self._local_env(call)

            blocks = 0
            resolved = True
            for spec in site.in_specs + site.out_specs:
                b = self._block_bytes(module, spec, defaults, consts)
                if b is None:
                    resolved = False
                    break
                blocks += b
            scratch = 0
            if resolved and site.scratch:
                for entry in site.scratch:
                    s = self._scratch_bytes(module, entry, env, defaults,
                                            consts)
                    if s is None:
                        resolved = False
                        break
                    scratch += s
            if not resolved or not (site.in_specs or site.out_specs):
                self.project.notes.append(
                    f"R6 {where} {site.kernel_name}: VMEM footprint not "
                    "statically resolvable — skipped")
                continue
            total = 2 * blocks + scratch
            self.project.notes.append(
                f"R6 {where} {site.kernel_name}: VMEM footprint "
                f"~{total / 1024:.0f} KiB ({blocks / 1024:.0f} KiB blocks "
                f"x2 double-buffered + {scratch / 1024:.0f} KiB scratch; "
                f"budget {budget / 1024:.0f} KiB)")
            if total > budget:
                out.append(self.finding(
                    module, call,
                    f"kernel {site.kernel_name} worst-case VMEM footprint "
                    f"{total} B (2x{blocks} block + {scratch} scratch) "
                    f"exceeds the {budget} B budget"))
        return out
