"""Symbolic models of every ``pl.pallas_call`` in ``repro.kernels``.

Interpret mode executes grid steps sequentially in Python and therefore
hides exactly the bug class that kills Pallas kernels on real TPUs:
output-block revisit races, out-of-bounds index maps, and uninitialized or
unflushed VMEM scratch accumulators. This module extracts a *static* model
of each kernel — grid, BlockSpec block shapes, index-map callables
(evaluated over enumerated grid coordinates and representative
scalar-prefetch operands), scratch shapes, and the kernel body's AST — so
``repro.analysis.kernel_verify`` can prove the hardware invariants without
any TPU.

Extraction works by interception: :func:`capture` monkeypatches
``pl.pallas_call`` while the ordinary kernel *wrapper* runs, records the
grid spec and the concrete operands the wrapper passes, and returns zeros
of ``out_shape`` instead of executing anything. The wrappers' own shape
logic (``_fit_block``, padding, GQA folding) is therefore modeled exactly
as shipped — there is no second copy of the launch math to drift.

Shape cases come from ``repro.configs``: :func:`config_cases` yields one
case per registered architecture with the *real* model dims (d_model,
head_dim, max_rank, rank block) so block shapes — and hence the VMEM
footprint table — match production, while batch/head/page counts are kept
small so exhaustive grid enumeration stays cheap (the index maps are
per-coordinate, so small grids exercise the same arithmetic).
"""
from __future__ import annotations

import ast
import dataclasses
import functools
import inspect
import textwrap
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

KERNEL_WRAPPERS = ("bgmv_shrink", "bgmv_expand", "mbgmv_shrink",
                   "mbgmv_expand", "flash_attention", "paged_attention")


@dataclasses.dataclass
class SpecModel:
    """One BlockSpec bound to its concrete operand."""
    block_shape: Tuple[int, ...]
    index_map: Callable
    shape: Tuple[int, ...]          # operand (or output) array shape
    dtype: Any                      # numpy dtype
    name: str                       # kernel ref param bound to this spec
    line: int                       # index_map lambda source line

    def nbytes(self) -> int:
        n = 1
        for d in self.block_shape:
            n *= int(d)
        return n * np.dtype(self.dtype).itemsize


@dataclasses.dataclass
class KernelModel:
    """Everything kernel_verify needs about one pallas_call site."""
    name: str                       # wrapper name (bgmv_shrink, ...)
    case: str                       # shape-case label (config name, ...)
    kernel_name: str                # kernel function name (_shrink_kernel)
    path: str                       # source file of the kernel function
    line: int                       # kernel def line (1-based)
    grid: Tuple[int, ...]
    num_scalar_prefetch: int
    scalars: List[np.ndarray]       # concrete scalar-prefetch operands
    in_specs: List[SpecModel]
    out_specs: List[SpecModel]
    scratch: List[Tuple[Tuple[int, ...], Any]]   # (shape, dtype)
    kernel_params: List[str]        # positional ref params of the kernel
    kernel_ast: Optional[ast.FunctionDef]
    ast_line_base: int              # kernel_ast lineno 1 == this file line
    in_groups: List[int]            # in_specs per input param (a param
                                    # bound to a sequence gets that many)

    # ---------------------------------------------------------------- ast --
    def abs_line(self, node: ast.AST) -> int:
        """Map a kernel_ast node line to an absolute file line."""
        return self.ast_line_base + getattr(node, "lineno", 1) - 1

    # ------------------------------------------------------------- params --
    def param_roles(self) -> Optional[Dict[str, str]]:
        """Map kernel param name -> scalar|input|output|scratch, or None if
        the signature does not line up with the captured specs."""
        nsp, ni = self.num_scalar_prefetch, len(self.in_groups)
        no, ns = len(self.out_specs), len(self.scratch)
        if len(self.kernel_params) != nsp + ni + no + ns:
            return None
        roles: Dict[str, str] = {}
        for i, p in enumerate(self.kernel_params):
            if i < nsp:
                roles[p] = "scalar"
            elif i < nsp + ni:
                roles[p] = "input"
            elif i < nsp + ni + no:
                roles[p] = "output"
            else:
                roles[p] = "scratch"
        return roles

    def scalar_param(self, k: int) -> Optional[str]:
        """Kernel ref param name of scalar-prefetch operand k."""
        if k < self.num_scalar_prefetch and k < len(self.kernel_params):
            return self.kernel_params[k]
        return None

    # --------------------------------------------------------- index maps --
    def eval_index(self, spec: SpecModel,
                   point: Tuple[int, ...]) -> Tuple[int, ...]:
        """Evaluate one index_map at a grid point with the representative
        scalar operands; returns concrete block coordinates."""
        out = spec.index_map(*point, *self.scalars)
        if not isinstance(out, tuple):
            out = (out,)
        return tuple(int(c) for c in out)

    def grid_points(self) -> Iterator[Tuple[int, ...]]:
        """Row-major (last dim fastest) — the TPU sequential grid order."""
        return np.ndindex(*self.grid)

    # --------------------------------------------------------------- vmem --
    def vmem_footprint(self) -> Dict[str, int]:
        """Per-grid-step VMEM bytes. ``total`` doubles the in/out windows
        for Pallas' pipeline double buffering; scratch is single-buffered
        (it persists across grid steps)."""
        in_b = sum(s.nbytes() for s in self.in_specs)
        out_b = sum(s.nbytes() for s in self.out_specs)
        sc_b = 0
        for shape, dtype in self.scratch:
            n = 1
            for d in shape:
                n *= int(d)
            sc_b += n * np.dtype(dtype).itemsize
        return {"in_bytes": in_b, "out_bytes": out_b,
                "scratch_bytes": sc_b,
                "total_bytes": 2 * (in_b + out_b) + sc_b}


# ------------------------------------------------------------------ capture --

def _unwrap(kernel) -> Callable:
    while isinstance(kernel, functools.partial):
        kernel = kernel.func
    return kernel


def _positional_params(fn: Callable) -> List[str]:
    out = []
    for p in inspect.signature(fn).parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            out.append(p.name)
    return out


_AST_CACHE: Dict[Tuple[str, int, str], Optional[ast.FunctionDef]] = {}


def _kernel_ast(fn: Callable) -> Tuple[Optional[ast.FunctionDef], str, int]:
    """(AST of fn's def, source path, first line). Best-effort: returns a
    None AST for callables without retrievable source (the numeric checks
    still run on such models)."""
    try:
        path = inspect.getsourcefile(fn) or "<unknown>"
        line = fn.__code__.co_firstlineno
    except (TypeError, AttributeError):
        return None, "<unknown>", 0
    key = (path, line, fn.__name__)
    if key not in _AST_CACHE:
        try:
            src = textwrap.dedent(inspect.getsource(fn))
            node = ast.parse(src).body[0]
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                node = None
        except (OSError, SyntaxError, IndexError):
            node = None
        _AST_CACHE[key] = node
    return _AST_CACHE[key], path, line


def lambda_line(fn: Callable) -> int:
    try:
        return fn.__code__.co_firstlineno
    except AttributeError:
        return 0


def _index_map(spec: pl.BlockSpec) -> Callable:
    """The index-map callable as written in the kernel wrapper: BlockSpec
    wraps it (``functools.update_wrapper``), and the verifier reads the
    lambda's source and closure, so unwrap to the original."""
    return inspect.unwrap(spec.index_map)


def _flat_specs(specs) -> List[pl.BlockSpec]:
    return list(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, pl.BlockSpec)))


def _spec_groups(specs) -> List[int]:
    """BlockSpecs per top-level entry of `specs`: Pallas hands the kernel
    one ref, or one sequence of refs, per entry."""
    if isinstance(specs, (list, tuple)):
        return [len(_flat_specs(s)) for s in specs]
    return [len(_flat_specs(specs))]


@contextmanager
def capture(into: List[KernelModel], *, name: str = "", case: str = ""):
    """Patch ``pl.pallas_call`` so wrapper invocations append a
    :class:`KernelModel` to `into` and return zeros instead of running."""
    real = pl.pallas_call

    def fake(kernel, out_shape, *, grid_spec=None, grid=(),
             in_specs=None, out_specs=None, scratch_shapes=(), **kw):
        if grid_spec is not None:
            g = tuple(grid_spec.grid)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
            groups = _spec_groups(grid_spec.in_specs)
            ins = _flat_specs(grid_spec.in_specs)
            outs = _flat_specs(grid_spec.out_specs)
            scratch = list(getattr(grid_spec, "scratch_shapes", ()) or ())
        else:
            g = tuple(grid)
            nsp = 0
            groups = _spec_groups(in_specs)
            ins = _flat_specs(in_specs)
            outs = _flat_specs(out_specs)
            scratch = list(scratch_shapes or ())
        kfn = _unwrap(kernel)
        kast, kpath, kline = _kernel_ast(kfn)
        out_structs = jax.tree_util.tree_leaves(out_shape)

        def runner(*operands):
            scalars = [np.asarray(o) for o in operands[:nsp]]
            tensors = jax.tree_util.tree_leaves(operands[nsp:])
            in_models = []
            for spec, op in zip(ins, tensors):
                imap = _index_map(spec)
                in_models.append(SpecModel(
                    block_shape=tuple(int(d) for d in spec.block_shape),
                    index_map=imap,
                    shape=tuple(op.shape),
                    dtype=np.dtype(op.dtype),
                    name="", line=lambda_line(imap)))
            out_models = []
            for spec, st in zip(outs, out_structs):
                imap = _index_map(spec)
                out_models.append(SpecModel(
                    block_shape=tuple(int(d) for d in spec.block_shape),
                    index_map=imap,
                    shape=tuple(st.shape),
                    dtype=np.dtype(st.dtype),
                    name="", line=lambda_line(imap)))
            params = _positional_params(kfn)
            model = KernelModel(
                name=name or kfn.__name__.lstrip("_"),
                case=case,
                kernel_name=kfn.__name__, path=kpath, line=kline,
                grid=g, num_scalar_prefetch=nsp, scalars=scalars,
                in_specs=in_models, out_specs=out_models,
                scratch=[(tuple(int(d) for d in s.shape),
                          np.dtype(s.dtype)) for s in scratch],
                kernel_params=params, kernel_ast=kast, ast_line_base=kline,
                in_groups=groups)
            # bind ref param names to specs (for messages); a param bound
            # to a sequence of specs names its leaves param[0], param[1], ...
            roles = model.param_roles()
            if roles is not None:
                leaves = iter(in_models)
                for gi, size in enumerate(groups):
                    for k in range(size):
                        sm = next(leaves)
                        sm.name = params[nsp + gi] + (
                            f"[{k}]" if size > 1 else "")
                for i, sm in enumerate(out_models):
                    sm.name = params[nsp + len(groups) + i]
            into.append(model)
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(out_shape),
                [jnp.zeros(s.shape, s.dtype) for s in out_structs])

        return runner

    pl.pallas_call = fake
    try:
        yield into
    finally:
        pl.pallas_call = real


# -------------------------------------------------------------- shape cases --

@dataclasses.dataclass(frozen=True)
class ShapeCase:
    """Representative dims for one extraction sweep. Block shapes (and the
    VMEM table) use the real model dims; batch/head/page counts are the
    minimum that still exercises GQA folding, block-table gathers, and
    no-adapter sentinels."""
    label: str
    d_model: int
    hd: int
    group: int                      # GQA group (H // KV) to model
    r_max: int
    rank_block: int
    ps: int = 32                    # KV page size (serving default sweep mid)
    dtype: Any = jnp.bfloat16
    has_attn: bool = True
    batch: int = 3
    pages: int = 6
    width: int = 3                  # block-table W
    seq: int = 512                  # flash prefill length (2 KV blocks)


def case_from_config(cfg) -> ShapeCase:
    group = 1
    has_attn = cfg.n_heads > 0 and cfg.n_kv_heads > 0
    if has_attn:
        group = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    # enumerate with 4 query heads, preserving whether GQA folds (group>1)
    group_e = group if group in (1, 2, 4) else 2
    return ShapeCase(
        label=cfg.name, d_model=cfg.d_model, hd=(cfg.hd if has_attn else 64),
        group=group_e, r_max=cfg.lora.max_rank,
        rank_block=cfg.lora.rank_block, dtype=cfg.jdtype,
        has_attn=has_attn)


def build_models(sc: ShapeCase) -> List[KernelModel]:
    """Run every kernel wrapper once under capture with `sc`'s shapes.
    Scalar operands include the full sentinel vocabulary: no-adapter rows
    (idx == -1), unclaimed pages (block_table == -1), an all-unclaimed row,
    maximal slot/page ids, and empty page slots (pos_pages == -1)."""
    from repro.kernels import bgmv, flash, mbgmv, paged

    models: List[KernelModel] = []
    slots, B = 3, sc.batch
    idx = jnp.asarray([0, slots - 1, -1][:B], jnp.int32)
    ranks = jnp.asarray([sc.r_max, min(sc.rank_block, sc.r_max), 1][:slots],
                        jnp.int32)
    x = jnp.zeros((B, sc.d_model), sc.dtype)
    a_pool = jnp.zeros((slots, sc.d_model, sc.r_max), sc.dtype)
    b_pool = jnp.zeros((slots, sc.r_max, sc.d_model), sc.dtype)
    y32 = jnp.zeros((B, sc.r_max), jnp.float32)

    with capture(models, name="bgmv_shrink", case=sc.label):
        bgmv.bgmv_shrink(x, a_pool, idx)
    with capture(models, name="bgmv_expand", case=sc.label):
        bgmv.bgmv_expand(y32.astype(sc.dtype), b_pool, idx)
    with capture(models, name="mbgmv_shrink", case=sc.label):
        mbgmv.mbgmv_shrink(x, a_pool, idx, ranks,
                           rank_block=sc.rank_block)
    with capture(models, name="mbgmv_expand", case=sc.label):
        mbgmv.mbgmv_expand(y32.astype(sc.dtype), b_pool, idx, ranks,
                           rank_block=sc.rank_block)

    if sc.has_attn:
        H = 4
        KV = max(1, H // sc.group)
        q = jnp.zeros((1, H, sc.seq, sc.hd), sc.dtype)
        k = jnp.zeros((1, KV, sc.seq, sc.hd), sc.dtype)
        with capture(models, name="flash_attention", case=sc.label):
            flash.flash_attention(q, k, k)

        P, W, ps = sc.pages, sc.width, sc.ps
        qd = jnp.zeros((B, H, sc.hd), sc.dtype)
        kp = jnp.zeros((P, KV, ps, sc.hd), sc.dtype)
        # pos_pages: page 0 fully empty (lazily grown), others part-filled
        pp = np.zeros((P, ps), np.int32)
        pp[0] = -1
        pp[1:, ps // 2:] = -1
        # block tables: max page id used, unclaimed tails, one row fully
        # unclaimed (the all-masked conformance edge)
        bt = np.full((B, W), -1, np.int32)
        order = [P - 1] + list(range(1, P - 1))
        it = iter(order)
        for b in range(B - 1):
            for j in range(min(W, 2)):
                try:
                    bt[b, j] = next(it)
                except StopIteration:
                    break
        pos = np.maximum(pp.max(axis=1).max(), 0) * np.ones(B, np.int32)
        with capture(models, name="paged_attention", case=sc.label):
            paged.paged_attention(qd, kp, kp, jnp.asarray(pp),
                                  jnp.asarray(bt), jnp.asarray(pos))
    return models


def lint_models() -> List[KernelModel]:
    """The representative sweep the lint's kernel-* rules run on: one dense
    GQA config (llama2-7b dims) — every kernel, every rule, small grids."""
    from repro.configs.base import get_config
    return build_models(case_from_config(get_config("llama2-7b")))


def config_cases() -> Iterator[ShapeCase]:
    """One ShapeCase per registered architecture (real dims)."""
    from repro.configs.base import all_arch_ids, get_config
    for name in all_arch_ids():
        yield case_from_config(get_config(name))


def config_models() -> Iterator[Tuple[str, List[KernelModel]]]:
    for sc in config_cases():
        yield sc.label, build_models(sc)
