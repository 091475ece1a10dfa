"""Compiled ("jit") execution backend for the query engine.

The numpy backend in ``operators.py`` interprets each expression node with
an intermediate array per node. This module lowers the same JSON pipeline
specs into fused kernels:

* Runs of ``filter``/``project`` operators compile into a small number of
  ``jax.jit`` functions per pipeline segment: every consecutive predicate
  fuses into ONE mask pass (a single XLA computation over just the
  referenced columns — no per-node numpy temporaries), rows compact once
  per mask (one gather per column), and each projection's derived columns
  evaluate in one fused computation over the already-compacted rows.
* ``hash_join`` (plus every following ``filter``/``project``, plus the
  shuffle's radix partition assignment when the run reaches the fragment
  output) lowers into ONE traced function (``_FusedTail``): the bucketed
  sorted probe (``kernels.hash_join``) locates each probe key in
  the argsorted build side, downstream predicates AND into the join's
  match mask with no intermediate materialization, derived projections
  evaluate over the probed columns, and the shuffle ``key % r`` partition
  assignment is computed in the same trace. The only numpy steps are the
  ones XLA's CPU backend loses badly on — the final stable partition
  permutation (np.argsort is a radix sort here, ~7x faster than XLA's
  sort) and the per-column output gathers, which also keep pass-through
  columns in their original dtype (no f64->f32 round-trip for data the
  trace never computes on). Each output column is gathered exactly once;
  the writer receives contiguous per-partition slices.
* Duplicate build keys stay compiled: a counts/prefix-sum pass over the
  bucketed range probe (``sorted_probe_range``) measures each probe
  row's match multiplicity, and a second traced call expands the
  multiplicity (``searchsorted`` over the prefix sums recovers, per
  output row, its probe row and build position), evaluates the
  downstream ops over the expanded rows, and assigns partitions — SQL
  inner-join semantics identical to ``op_hash_join``, in-trace.
* A trailing ``hash_agg`` no longer splits a shuffle fragment's trace:
  when the partition key is one of the agg's group keys, the partition
  assignment commutes with the (per-fragment, partial) aggregation, so
  the preceding ``[hash_join?] + (filter|project)*`` segment fuses WITH
  the partition assignment into one traced call and the aggregation then
  runs per partition slice — partial pre-agg shuffle plans (the
  optimizer's agg split) execute as one traced call per segment.
* ``hash_agg`` gives each row a group id and hands the aggregate columns,
  in input order, to the Pallas segmented-reduction kernel
  (``kernels.segment_reduce``), stacked so all same-mode aggregates
  reduce in a single kernel launch — interpret mode on CPU, Mosaic on
  TPU, like the kernels in ``kernels/ops.py``. Integer keys whose value
  ranges multiply to at most ``_MAX_KERNEL_GROUPS`` group by their
  mixed-radix code, with no sort (``_dense_groups``); other keys are
  lexsorted and each sorted row's group id is scattered back to its
  input position. Past that many groups, sort and ``reduceat`` on the
  host.
* ``udf`` operators run the UDF's traced implementation where
  ``_TRACED_UDFS`` has one, through the device boundary: TPCx-BB Q3's
  session window is one jitted program (``kernels.session_window``) over
  the fragment's whole user sessions. A UDF with no traced
  implementation, or a batch outside its int32 program, runs the numpy
  function (``operators``), which stays the reference.

Fragments call ``run_pipeline_partition`` so the shuffle partition fuses
into the trailing compiled segment on the jit backend; the numpy backend
keeps the interpreted operators plus ``operators.radix_partition`` as the
semantic reference. Joins whose key or referenced columns overflow the
int32 jit boundary fall back to ``op_hash_join`` with identical semantics
(with a loud one-time ``RuntimeWarning`` — the fallback is correct but
interpreted).

Compiled segments are cached on the JSON text of their specs, so the many
fragments of one pipeline share a single compilation.

Float contract (this backend is the DEFAULT; ``docs/BACKENDS.md`` is the
user-facing version): XLA executes in float32 here (x64 stays disabled
for the model stack), but aggregate sums accumulate PAIRWISE in the
segmented-reduction kernel, so aggregates match the float64 numpy backend
at rtol=1e-6 (the parity suite pins that tolerance). A float64 value
within float32 epsilon of a predicate constant can still land on the
other side of a fused filter — row sets may differ at such knife-edge
boundaries (TPC data is quantized to 2 decimals, far coarser than that).
Integer columns likewise narrow to int32 at the jit boundary — fused
segments whose referenced int64 columns hold values beyond int32 range,
and projections whose derived expressions stay in integer arithmetic,
fall back to the interpreted path rather than silently truncate (see
``_run_fused`` / ``_int_valued``). Full-width execution is a ROADMAP
follow-up (local x64).
"""
from __future__ import annotations

import collections
import functools
import json
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.spans import span, spanned
from repro.engine import logical as engine_logical
from repro.engine import operators
from repro.engine import plans as engine_plans
from repro.engine.columnar import ColumnBatch
from repro.kernels import hash_join as hj_kernel
from repro.kernels import session_window as sw_kernel
from repro.kernels import tpu
from repro.kernels.segment_reduce import segment_reduce


# ---------------------------------------------------------------------------
# Device boundary
# ---------------------------------------------------------------------------

def _on_device(kernel: str, fn, *args, **kwargs):
    """Call a jitted function or kernel and copy its outputs to host
    numpy, inside one ``engine.device`` span: the time the host is
    blocked on the chip, transfers included. ``h2d_bytes`` counts the
    host arrays handed in, at the width the device receives them."""
    h2d = sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
              for a in jax.tree.leaves(args)
              if isinstance(a, (np.ndarray, np.generic)))
    with span("engine.device", kernel=kernel, h2d_bytes=h2d):
        return jax.tree.map(np.asarray, fn(*args, **kwargs))


# ---------------------------------------------------------------------------
# Expression analysis (evaluation itself is shared: operators.eval_expr /
# eval_value traced with xp=jnp; the referenced-column walkers are shared
# with the logical planner so the two layers cannot drift on the grammar)
# ---------------------------------------------------------------------------

_expr_refs = engine_logical.pred_columns
_value_refs = engine_logical.value_columns


def _expr_consts(expr, out: list):
    """Literal comparison values in a predicate expression."""
    op = expr[0]
    if op in ("and", "or"):
        for sub in expr[1:]:
            _expr_consts(sub, out)
    elif op == "between":
        out.extend(expr[2:4])
    elif op == "in":
        out.extend(expr[2])
    elif op != "ltcol":   # lt | le | ge | gt | eq | ne
        out.append(expr[2])
    return out


def _value_consts(expr, out: list):
    """Literal constants in a value expression."""
    if isinstance(expr, str):
        return out
    op = expr[0]
    if op == "const":
        out.append(expr[1])
    elif op in ("mul", "add", "sub", "div"):
        _value_consts(expr[1], out)
        _value_consts(expr[2], out)
    elif op in ("sub1", "add1"):
        _value_consts(expr[1], out)
    elif op == "case_in":
        out.extend(expr[2])
    return out


# ---------------------------------------------------------------------------
# Canonical literals (the compiled-plan cache boundary)
# ---------------------------------------------------------------------------
#
# Segments and tails compile from CANONICAL op specs
# (``plans.canonicalize_ops``): literal values are replaced by positional
# ``[plans.LIT, i, tag]`` placeholders and arrive per call as a separate
# binding, so the module-level trace caches key on plan SHAPE — two
# queries that differ only in filter constants / projection coefficients
# / in-list values (same length, same dtype class) fetch the same
# compiled object AND reuse its XLA traces. Inside a jit trace the
# binding is a tuple of fixed-dtype scalars/arrays (jit specializes on
# dtype+shape, not value); on interpreted fallbacks and host-side const
# evaluation it is the original Python values (numpy dtype semantics
# preserved, e.g. ``np.full`` of a Python float stays float64).

def _subst(node, vals):
    """Re-bind placeholder nodes to concrete values: original literals
    for interpreted/host evaluation, traced values inside a trace."""
    if isinstance(node, (list, tuple)):
        if len(node) == 3 and node[0] == engine_plans.LIT:
            return vals[node[1]]
        return [_subst(x, vals) for x in node]
    return node


def _lit_indices(node, out: set) -> set:
    if isinstance(node, (list, tuple)):
        if len(node) == 3 and node[0] == engine_plans.LIT:
            out.add(node[1])
        else:
            for x in node:
                _lit_indices(x, out)
    return out


def _flat_lits(vals) -> list:
    """Scalar view of literal values (list literals flatten) for the
    wide-int guards."""
    out: list = []
    for v in vals:
        if isinstance(v, list):
            out.extend(v)
        else:
            out.append(v)
    return out


def _narrow_lits(lits) -> tuple:
    """The traced literal binding: fixed dtypes (bool / int32 / float32,
    matching what x64-off narrowing did to the formerly baked constants)
    so every shape-compatible binding hits the same trace. Integers
    beyond int32 widen to int64 — the stage that actually references a
    wide literal has already diverted to its interpreted path, and an
    int64 scalar in an unused argument slot only costs a one-off trace."""
    out = []
    for v in lits:
        if isinstance(v, list):
            a = np.asarray(v)
            if a.dtype.kind == "f":
                a = a.astype(np.float32)
            elif a.dtype.kind in "iu" and not _any_wide_int(v):
                a = a.astype(np.int32)
            out.append(a)
        elif isinstance(v, bool):
            out.append(np.bool_(v))
        elif isinstance(v, int):
            out.append(np.int64(v) if not _INT32_MIN <= v <= _INT32_MAX
                       else np.int32(v))
        else:
            out.append(np.float32(v))
    return tuple(out)


# ---------------------------------------------------------------------------
# Fused filter/project segments
# ---------------------------------------------------------------------------
#
# A segment (maximal run of filter/project ops) compiles into stages over a
# numpy column environment:
#   MaskStage    — all consecutive predicates fused into one jitted mask
#                  evaluation over the referenced columns, then one
#                  compaction gather per live column;
#   ProjectStage — pass-throughs are moved (no copy), constant outputs are
#                  filled in numpy, and the derived expressions evaluate in
#                  one jitted computation over the (compacted) inputs.
# Feeding jit only the referenced columns keeps the f64->f32 dispatch
# conversion off the untouched columns.

# XLA specializes on input length, and fragment row counts are
# data-dependent, so an unbounded shape set would recompile per fragment.
# The first few raw lengths trace directly (steady-state fragments reuse
# them at zero padding cost); further new lengths pad up to a power of
# two, capping total traces per stage at _MAX_RAW_SHAPES + log2(rows).
_MAX_RAW_SHAPES = 4


def _bounded_shape(cols: dict, n: int, seen: set):
    if n in seen or len(seen) < _MAX_RAW_SHAPES:
        seen.add(n)
        return cols, n
    n_pad = _pow2(n)
    if n_pad == n:
        return cols, n
    return {k: np.concatenate([v, np.zeros(n_pad - n, v.dtype)])
            for k, v in cols.items()}, n_pad


class _MaskStage:
    def __init__(self, exprs: list):
        self.exprs = exprs                   # canonical (placeholder) form
        self.refs = sorted(set().union(
            *[_expr_refs(e, set()) for e in exprs]))
        self._lit_idx = sorted(_lit_indices(exprs, set()))
        self._seen: set = set()

        @jax.jit
        def mask_fn(cols, lits):
            bound = [_subst(e, lits) for e in exprs]
            m = operators.eval_expr(bound[0], cols, xp=jnp)
            for e in bound[1:]:
                m = m & operators.eval_expr(e, cols, xp=jnp)
            return m

        self._fn = mask_fn

    def run(self, env: dict, lits: list = ()) -> dict:
        own = _flat_lits([lits[i] for i in self._lit_idx])
        if _any_wide_int(own) or \
                any(_overflows_int32(env[k]) for k in self.refs):
            # int32 narrowing would flip the comparison: evaluate the
            # predicates interpreted instead (original literal values).
            bound = [_subst(e, lits) for e in self.exprs]
            mask = operators.eval_expr(bound[0], env)
            for e in bound[1:]:
                mask = mask & operators.eval_expr(e, env)
        else:
            n = len(next(iter(env.values())))
            cols, _ = _bounded_shape({k: env[k] for k in self.refs}, n,
                                     self._seen)
            mask = _on_device("mask", self._fn, cols,
                              _narrow_lits(lits))[:n]
        idx = np.flatnonzero(mask)
        return {k: v[idx] for k, v in env.items()}


def _int_valued(expr, env: dict) -> bool:
    """True when numpy would evaluate ``expr`` in integer arithmetic —
    which the jit path would narrow to int32 and silently overflow."""
    if isinstance(expr, str):
        return env[expr].dtype.kind in "iu"
    op = expr[0]
    if op == "const":
        return isinstance(expr[1], (int, np.integer)) \
            and not isinstance(expr[1], bool)
    if op in ("mul", "add", "sub"):
        return _int_valued(expr[1], env) and _int_valued(expr[2], env)
    return False   # div / sub1 / add1 / case_in produce floats


class _ProjectStage:
    def __init__(self, columns: list):
        self.columns = columns               # canonical (placeholder) form
        self.passthrough = [c for c in columns if isinstance(c, str)]
        derived = [(c[0], c[1]) for c in columns if not isinstance(c, str)]
        self.consts = [(name, expr) for name, expr in derived
                       if not _value_refs(expr, set())]
        self.computed = [(name, expr) for name, expr in derived
                         if _value_refs(expr, set())]
        self.refs = sorted(set().union(
            set(), *[_value_refs(e, set()) for _, e in self.computed]))
        self.order = [c if isinstance(c, str) else c[0] for c in columns]
        self._lit_idx = sorted(_lit_indices(
            [e for _, e in self.computed], set()))
        self._seen: set = set()

        computed = self.computed

        @jax.jit
        def project_fn(cols, lits):
            n = next(iter(cols.values())).shape[0]
            out = {}
            for name, expr in computed:
                v = operators.eval_value(_subst(expr, lits), cols, xp=jnp)
                out[name] = jnp.broadcast_to(v, (n,)) if v.ndim == 0 else v
            return out

        self._fn = project_fn if computed else None

    def run(self, env: dict, lits: list = ()) -> dict:
        own = _flat_lits([lits[i] for i in self._lit_idx])
        computed_host = [(name, _subst(e, lits)) for name, e in
                         self.computed]
        if _any_wide_int(own) \
                or any(_overflows_int32(env[k]) for k in self.refs) \
                or any(_int_valued(e, env) for _, e in computed_host):
            # int32 narrowing of wide inputs, wide literals, or derived
            # integer arithmetic would corrupt values; evaluate the whole
            # projection interpreted (rare — TPC derived columns are
            # float arithmetic over in-range data).
            return dict(operators.op_project(ColumnBatch(env),
                                             _subst(self.columns, lits)))
        n = len(next(iter(env.values()))) if env else 0
        out = {name: env[name] for name in self.passthrough}
        for name, expr in self.consts:
            # Host-side constant fill with the ORIGINAL literal value:
            # np.full of a Python float keeps the numpy backend's float64
            # output dtype.
            out[name] = np.full(
                n, np.asarray(operators.eval_value(_subst(expr, lits),
                                                   ColumnBatch({}))))
        if self._fn is not None:
            cols, _ = _bounded_shape({k: env[k] for k in self.refs}, n,
                                     self._seen)
            for name, v in _on_device("project", self._fn, cols,
                                      _narrow_lits(lits)).items():
                out[name] = v[:n]
        return {name: out[name] for name in self.order}


@functools.lru_cache(maxsize=256)
def _compile_segment(segment_json: str):
    """Compiled stages for a CANONICAL segment JSON (literal values are
    placeholder nodes, so shape-compatible queries share one entry)."""
    segment = json.loads(segment_json)
    stages = []
    i = 0
    while i < len(segment):
        if segment[i]["op"] == "filter":
            exprs = []
            while i < len(segment) and segment[i]["op"] == "filter":
                exprs.append(segment[i]["expr"])
                i += 1
            stages.append(_MaskStage(exprs))
        else:
            stages.append(_ProjectStage(segment[i]["columns"]))
            i += 1
    return stages


def _canon_json(ops: list[dict]) -> tuple[str, list]:
    canon, lits = engine_plans.canonicalize_ops(ops)
    return json.dumps(canon, sort_keys=True), lits


_INT32_MAX = np.iinfo(np.int32).max
_INT32_MIN = np.iinfo(np.int32).min

# The int32 fallback warning fires once per process: silent per-fragment
# warnings would flood a query's log, silence would hide that a "jit"
# query is quietly running its joins interpreted. The counter counts every
# such fallback (per fragment call), for callers that must see each one;
# ``int32_udf`` counts UDF calls whose traced implementation refused the
# batch and ran numpy instead.
_INT32_FALLBACK_WARNED = False
FALLBACK_STATS = {"int32_join": 0, "int32_udf": 0}


def _warn_int32_fallback(detail: str) -> None:
    global _INT32_FALLBACK_WARNED
    FALLBACK_STATS["int32_join"] += 1
    if _INT32_FALLBACK_WARNED:
        return
    _INT32_FALLBACK_WARNED = True
    warnings.warn(
        "jit backend: a compiled hash_join fell back to the interpreted "
        f"numpy reference ({detail}). The compiled probe narrows keys "
        "and referenced columns to int32; wider values execute on numpy "
        "instead — results are identical but the fragment runs at "
        "interpreted speed. Emitted once per process; see "
        "docs/BACKENDS.md for the full fallback matrix.",
        RuntimeWarning, stacklevel=2)


def _overflows_int32(v: np.ndarray) -> bool:
    if v.dtype.kind not in "iu" or v.size == 0:
        return False
    if v.dtype.itemsize <= 4 and v.dtype != np.uint32:
        return False   # int32 and narrower always fit
    return bool(v.max() > _INT32_MAX or v.min() < _INT32_MIN)


def _any_wide_int(consts: list) -> bool:
    return any(isinstance(c, (int, np.integer))
               and not isinstance(c, bool)
               and not _INT32_MIN <= c <= _INT32_MAX for c in consts)


def _run_fused(batch: ColumnBatch, segment: list[dict]) -> ColumnBatch:
    if batch.num_rows == 0 or not len(batch):
        # Empty (possibly schema-less) inputs keep the interpreted path's
        # empty-batch semantics.
        return operators.run_pipeline_ops(batch, segment)
    # Per-stage int32-narrowing guards live in the stages themselves (a
    # stage may consume wide integers produced by an earlier one).
    canon, lits = _canon_json(segment)
    env = {k: np.asarray(v) for k, v in batch.items()}
    for stage in _compile_segment(canon):
        env = stage.run(env, lits)
    return ColumnBatch(env)


# ---------------------------------------------------------------------------
# Fused join -> ops -> partition tail
# ---------------------------------------------------------------------------
#
# A tail is ``[hash_join?] + (filter|project)*`` optionally terminated by
# the fragment's shuffle partition. One traced function computes the probe
# (bucketed sorted probe over the argsorted build side), the fused
# predicate mask, every derived projection, and the radix partition
# assignment (``r`` = dead-row sentinel for unmatched/filtered rows); the
# host then derives the stable partition permutation with one radix
# argsort and gathers each surviving output column exactly once — from
# the ORIGINAL arrays for pass-through columns (dtype preserved) and from
# the trace outputs for derived ones.
#
# Duplicate build keys take a two-trace variant of the same shape (the
# output row count is data-dependent, so it must cross the host once):
# trace 1 range-probes every key (``sorted_probe_range``) and returns the
# per-row match multiplicity; the host prefix-sums the counts (the only
# host step — one cumsum); trace 2 expands the multiplicity in-trace
# (``searchsorted`` over the prefix recovers each output row's probe row
# ``i`` and build position ``lo[i] + offset``), evaluates the downstream
# ops over the expanded rows, and assigns partitions. Matches are emitted
# in build sort order within a probe row and probe rows stay in probe
# order — byte-identical to ``operators.op_hash_join``.

def _int_valued_sim(expr, int_kinds: dict) -> bool:
    """``operators``-free mirror of ``_int_valued`` over a simulated
    schema (column name -> is-integer-kind)."""
    if isinstance(expr, str):
        return int_kinds[expr]
    op = expr[0]
    if op == "const":
        return isinstance(expr[1], (int, np.integer)) \
            and not isinstance(expr[1], bool)
    if op in ("mul", "add", "sub"):
        return _int_valued_sim(expr[1], int_kinds) \
            and _int_valued_sim(expr[2], int_kinds)
    return False


class _FusedTail:
    """Compiled ``[hash_join?] + (filter|project)*`` (+ optional radix
    partition) — see the section comment above."""

    def __init__(self, segment: list[dict], partition):
        self.segment = segment               # canonical (placeholder) form
        self.partition = partition           # (key_col, partitions) | None
        self.join = segment[0] if segment and segment[0]["op"] == "hash_join" \
            else None
        self.ops = segment[1:] if self.join else segment
        self._seen_probe: set = set()
        self._seen_build: set = set()
        self._seen_out: set = set()      # expanded-row counts (dup joins)
        self._fns: dict = {}
        # Last build-side prep (argsort, bucket index, payload gathers),
        # keyed by the identity of the build's key array. Morsel-wise
        # probing runs the same tail many times against one build; the
        # held reference keeps the key array alive so an `is` check can
        # never false-positive on a recycled id.
        self._build_prep: Optional[tuple] = None

    # -- plan analysis (per input schema) ----------------------------------
    def _resolve_needed(self, left_names, right_names):
        """Walk the ops over a name-level schema. Returns
        ``(final_sources, left_in, right_in)``: the origin of every final
        output column ('left'|'right'|'derived'|'const') and the concrete
        left/right columns the traced function must receive (expression
        references plus the join and partition keys); derived columns are
        recomputed inside the trace in op order."""
        left_in, right_in = set(), set()
        sources = {c: ("left", c) for c in left_names}
        if self.join:
            left_in.add(self.join["left_key"])
            for c in right_names:
                if c != self.join["right_key"]:
                    sources[c] = ("right", c)
        # A needed name resolves against the schema at its reference
        # point; walking ops in order and resolving eagerly is equivalent
        # because project() rebinds names before later references.
        for op in self.ops:
            if op["op"] == "filter":
                for r in _expr_refs(op["expr"], set()):
                    src = sources[r]
                    if src[0] == "left":
                        left_in.add(src[1])
                    elif src[0] == "right":
                        right_in.add(src[1])
            else:
                new = {}
                for c in op["columns"]:
                    if isinstance(c, str):
                        new[c] = sources[c]
                    else:
                        name, expr = c[0], c[1]
                        for r in _value_refs(expr, set()):
                            src = sources[r]
                            if src[0] == "left":
                                left_in.add(src[1])
                            elif src[0] == "right":
                                right_in.add(src[1])
                        new[name] = ("derived", expr) \
                            if _value_refs(expr, set()) else ("const", expr)
                sources = new
        if self.partition is not None:
            src = sources[self.partition[0]]
            if src[0] == "left":
                left_in.add(src[1])
            elif src[0] == "right":
                right_in.add(src[1])
        return sources, sorted(left_in), sorted(right_in)

    # -- guards -------------------------------------------------------------
    def _must_fall_back(self, batch, build, left_in, right_in,
                        sources_host, ops_host, wide_lits) -> bool:
        if wide_lits:
            return True
        if batch.num_rows == 0 or not len(batch):
            return True
        if self.join is not None:
            if build.num_rows == 0 or not len(build):
                return True
            lk = np.asarray(batch[self.join["left_key"]])
            rk = np.asarray(build[self.join["right_key"]])
            if lk.dtype.kind not in "iu" or rk.dtype.kind not in "iu":
                return True
            for name, vals in ((self.join["left_key"], lk),
                               (self.join["right_key"], rk)):
                if _overflows_int32(vals):
                    _warn_int32_fallback(
                        f"join key column {name!r} exceeds int32 range "
                        f"(max value {int(vals.max())}, "
                        f"min value {int(vals.min())})")
                    return True
        for c in left_in:
            v = np.asarray(batch[c])
            if _overflows_int32(v):
                if self.join is not None:
                    _warn_int32_fallback(
                        f"probe-side column {c!r} exceeds int32 range "
                        f"(max value {int(v.max())})")
                return True
        for c in right_in:
            v = np.asarray(build[c])
            if _overflows_int32(v):
                if self.join is not None:
                    _warn_int32_fallback(
                        f"build-side column {c!r} exceeds int32 range "
                        f"(max value {int(v.max())})")
                return True
        # Derived integer arithmetic would narrow to int32 (mirrors
        # _ProjectStage's guard) — simulate dtype kinds through the ops
        # (literal-substituted form: placeholders carry no type info).
        int_kinds = {c: np.asarray(v).dtype.kind in "iu"
                     for c, v in batch.items()}
        if self.join is not None:
            for c, v in build.items():
                if c != self.join["right_key"]:
                    int_kinds[c] = np.asarray(v).dtype.kind in "iu"
        for op in ops_host:
            if op["op"] != "project":
                continue
            kinds = {}
            for c in op["columns"]:
                if isinstance(c, str):
                    kinds[c] = int_kinds[c]
                else:
                    name, expr = c[0], c[1]
                    iv = _int_valued_sim(expr, int_kinds)
                    if iv and _value_refs(expr, set()):
                        return True
                    kinds[name] = iv
            int_kinds = kinds
        if self.partition is not None:
            src = sources_host[self.partition[0]]
            if src[0] == "const":
                v = operators.eval_value(src[1], ColumnBatch({}))
                if np.asarray(v).dtype.kind not in "iu":
                    return True
            elif not int_kinds[self.partition[0]]:
                return True   # numpy truncates float keys; keep its path
        return False

    def _host_ops(self, lits) -> list[dict]:
        """The segment's ops with original literal values re-bound — what
        the interpreted fallback and host-side guards evaluate."""
        out = []
        for op in self.ops:
            if op["op"] == "filter":
                out.append({"op": "filter", "expr": _subst(op["expr"],
                                                           lits)})
            elif op["op"] == "project":
                out.append({"op": "project",
                            "columns": _subst(op["columns"], lits)})
            else:
                out.append(op)
        return out

    def _numpy_tail(self, batch, build, ops_host):
        if self.join is not None:
            batch = operators.op_hash_join(batch, build,
                                           self.join["left_key"],
                                           self.join["right_key"])
        batch = operators.run_pipeline_ops(batch, ops_host)
        if self.partition is not None:
            return operators.radix_partition(batch, self.partition[0],
                                             self.partition[1])
        return batch

    # -- traced functions ---------------------------------------------------
    def _trace_ops(self, sources, env, match, n, lits):
        """Shared trace body (pure; called inside jit): fused predicate
        mask, derived projections, and the partition assignment over an
        env of traced columns. ``lits`` is the traced literal binding —
        placeholder nodes re-bind to traced scalars here, so literal
        values never bake into the trace."""
        for op in self.ops:
            if op["op"] == "filter":
                match = match & operators.eval_expr(
                    _subst(op["expr"], lits), env, xp=jnp)
            else:
                new = dict(env)        # keep shadowed inputs reachable for
                for c in op["columns"]:            # later env lookups
                    if not isinstance(c, str):
                        v = operators.eval_value(_subst(c[1], lits), env,
                                                 xp=jnp)
                        new[c[0]] = jnp.broadcast_to(v, (n,)) \
                            if v.ndim == 0 else v
                env = new
        if self.partition is not None:
            key, nparts = self.partition[0], self.partition[1]
            src = sources[key]
            if src[0] == "const":
                kv = operators.eval_value(_subst(src[1], lits),
                                          env, xp=jnp)
                assign = jnp.where(match,
                                   kv.astype(jnp.int32) % nparts, nparts)
            else:
                assign = jnp.where(
                    match, env[key].astype(jnp.int32) % nparts, nparts)
        else:
            assign = jnp.where(match, 0, 1)
        derived_out = sorted(nm for nm, s in sources.items()
                             if s[0] == "derived")
        return assign.astype(jnp.int32), {nm: env[nm] for nm in derived_out}

    def _build_fn(self, sources, left_in, right_in, needs_pos):
        join = self.join
        trace_ops = self._trace_ops

        @functools.partial(jax.jit, static_argnames=("iters", "r"))
        def fn(left_cols, lits, bkeys, bpayload, scalars, starts, n_valid,
               *, iters, r):
            n = next(iter(left_cols.values())).shape[0]
            valid = jnp.arange(n, dtype=jnp.int32) < n_valid
            env = dict(left_cols)
            pos = None
            if join is not None:
                pos, match = hj_kernel.sorted_probe(
                    bkeys, env[join["left_key"]].astype(jnp.int32),
                    scalars=scalars, starts=starts, iters=iters)
                match = match & valid
                for c in right_in:
                    env[c] = bpayload[c][pos]
            else:
                match = valid
            assign, out = trace_ops(sources, env, match, n, lits)
            res = (assign, out)
            return res + ((pos,) if needs_pos else ())

        return fn

    def _build_count_fn(self):
        """Dup-key trace 1: range-probe every key, return the lower-bound
        positions and the per-probe-row match multiplicities."""
        @functools.partial(jax.jit, static_argnames=("iters",))
        def count_fn(lkeys, bkeys, scalars, starts, n_valid, *, iters):
            n = lkeys.shape[0]
            valid = jnp.arange(n, dtype=jnp.int32) < n_valid
            lo, hi, match = hj_kernel.sorted_probe_range(
                bkeys, lkeys.astype(jnp.int32), scalars=scalars,
                starts=starts, iters=iters)
            return lo, jnp.where(match & valid, hi - lo, 0)

        return count_fn

    def _build_expand_fn(self, sources, left_in, right_in):
        """Dup-key trace 2: expand the match multiplicity (output row j
        belongs to probe row ``i = searchsorted(prefix, j) - 1`` at build
        position ``lo[i] + (j - prefix[i])``), then run the fused ops and
        partition assignment over the expanded rows."""
        trace_ops = self._trace_ops

        @functools.partial(jax.jit, static_argnames=("r", "n_out"))
        def expand_fn(left_cols, lits, bpayload, lo, prefix, total,
                      *, r, n_out):
            j = jnp.arange(n_out, dtype=jnp.int32)
            i = jnp.clip(
                jnp.searchsorted(prefix, j, side="right").astype(jnp.int32)
                - 1, 0, lo.shape[0] - 1)
            valid = j < total
            rpos = lo[i] + (j - prefix[i])
            env = {c: left_cols[c][i] for c in left_in}
            for c in right_in:
                env[c] = bpayload[c][rpos]
            assign, out = trace_ops(sources, env, valid, n_out, lits)
            return assign, out, i, rpos

        return expand_fn

    # -- host finalization --------------------------------------------------
    @staticmethod
    def _stable_partition(assign: np.ndarray, r: int):
        """One radix argsort for the stable partition permutation."""
        lividx = np.flatnonzero(assign < r)
        if r == 1:
            return lividx, np.asarray([len(lividx)])   # already in order
        order = lividx[np.argsort(assign[lividx], kind="stable")]
        return order, np.bincount(assign[lividx], minlength=r)

    def _gather_out(self, batch, bpay_out, sources_host, derived, order,
                    left_sel, right_sel, nrows):
        """Exactly one gather per output column — from the ORIGINAL
        arrays for pass-through columns (dtype preserved), from the
        trace outputs for derived ones. ``sources_host`` carries the
        original (un-placeholdered) literal values so const fills keep
        numpy dtype semantics."""
        out = {}
        for name, src in sources_host.items():
            if src[0] == "left":
                out[name] = np.asarray(batch[src[1]])[left_sel]
            elif src[0] == "right":
                out[name] = bpay_out[src[1]][right_sel]
            elif src[0] == "derived":
                out[name] = np.asarray(derived[name])[:nrows][order]
            else:   # const: numpy dtype semantics (np.full of a scalar)
                out[name] = np.full(len(order), np.asarray(
                    operators.eval_value(src[1], ColumnBatch({}))))
        return out

    def _emit(self, out: dict, counts: np.ndarray, r: int):
        if self.partition is None:
            return ColumnBatch(out)
        bounds = np.concatenate(([0], np.cumsum(counts)))
        return [ColumnBatch({k: v[bounds[p]:bounds[p + 1]]
                             for k, v in out.items()})
                for p in range(r)]

    # -- execution ----------------------------------------------------------
    def run(self, batch: ColumnBatch, build, lits=()):
        left_names = list(batch)
        right_names = list(build) if build is not None else []
        final_sources, left_in, right_in = self._resolve_needed(
            left_names, right_names)
        ops_host = self._host_ops(lits)
        sources_host = {k: ((s[0], _subst(s[1], lits)) if s[0] == "const"
                            else s)
                        for k, s in final_sources.items()}
        traced_work = self.join is not None \
            or any(op["op"] == "filter" for op in self.ops) \
            or any(s[0] == "derived" for s in final_sources.values())
        if not traced_work or not left_in:
            return self._numpy_tail(batch, build, ops_host)
        wide_lits = _any_wide_int(_flat_lits(lits))
        if self._must_fall_back(batch, build, left_in, right_in,
                                sources_host, ops_host, wide_lits):
            return self._numpy_tail(batch, build, ops_host)
        lits_t = _narrow_lits(lits)

        n = batch.num_rows
        r = self.partition[1] if self.partition is not None else 1
        needs_pos = any(s[0] == "right" for s in final_sources.values())

        # Host-side build prep: argsort + bucket index for the probe.
        # Memoized on the build key array's identity: out-of-core morsel
        # streaming probes one build with many small batches, and the
        # O(build) argsort + payload gathers must not be paid per morsel.
        bkeys_pad = scalars = starts = None
        bpay_sorted: dict = {}
        bpay_out: dict = {}
        iters = 0
        has_dups = False
        if self.join is not None:
            rkeys = np.asarray(build[self.join["right_key"]])
            out_cols = {src[1] for src in final_sources.values()
                        if src[0] == "right"}
            prep_key = (tuple(right_in), tuple(sorted(out_cols)))
            if self._build_prep is not None \
                    and self._build_prep[0] is rkeys \
                    and self._build_prep[1] == prep_key:
                (bkeys_pad, has_dups, scalars, starts, iters,
                 bpay_sorted, bpay_out) = self._build_prep[2]
            else:
                border = np.argsort(rkeys, kind="stable")
                bs = rkeys[border].astype(np.int32)
                has_dups = bool(bs[1:].size
                                and np.any(bs[1:] == bs[:-1]))
                scalars, starts, iters = hj_kernel.prepare_buckets(bs)
                s = len(bs)
                s_pad = s if s in self._seen_build or \
                    len(self._seen_build) < _MAX_RAW_SHAPES else _pow2(s)
                self._seen_build.add(s)
                if s_pad > s:
                    bs = np.concatenate(
                        [bs, np.full(s_pad - s, hj_kernel._INT32_MAX,
                                     np.int32)])
                bkeys_pad = bs
                # One gather per needed payload column: the unpadded
                # sorted copy serves the host-side pass-through outputs
                # (original dtype preserved), a padded view of the same
                # array feeds the trace.
                for c in sorted(set(right_in) | out_cols):
                    v = np.asarray(build[c])[border]
                    if c in out_cols:
                        bpay_out[c] = v
                    if c in right_in:
                        bpay_sorted[c] = v if s_pad == s \
                            else np.concatenate(
                                [v, np.zeros(s_pad - s, v.dtype)])
                self._build_prep = (rkeys, prep_key,
                                    (bkeys_pad, has_dups, scalars, starts,
                                     iters, bpay_sorted, bpay_out))

        left_cols, _ = _bounded_shape(
            {c: np.asarray(batch[c]) for c in left_in}, n, self._seen_probe)

        if has_dups:
            return self._run_dup(batch, final_sources, sources_host,
                                 left_in, right_in, left_cols, lits_t,
                                 bkeys_pad, bpay_sorted, bpay_out,
                                 scalars, starts, iters, n, r,
                                 build, ops_host, (tuple(left_names),
                                                   tuple(right_names)))

        key = (tuple(left_names), tuple(right_names), needs_pos)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build_fn(final_sources, left_in, right_in, needs_pos)
            self._fns[key] = fn
        res = _on_device("join_probe" if self.join else "tail", fn,
                         left_cols, lits_t, bkeys_pad, bpay_sorted, scalars,
                         starts, np.int32(n), iters=iters, r=r)
        assign = res[0][:n]
        derived = res[1]
        pos = res[2][:n] if needs_pos else None

        order, counts = self._stable_partition(assign, r)
        out = self._gather_out(batch, bpay_out, sources_host, derived,
                               order, order,
                               pos[order] if pos is not None else None, n)
        return self._emit(out, counts, r)

    def _run_dup(self, batch, sources, sources_host, left_in, right_in,
                 left_cols, lits_t, bkeys_pad, bpay_sorted, bpay_out,
                 scalars, starts, iters, n, r, build, ops_host, schema_key):
        """Compiled duplicate-build-key join: counts/prefix pass, then the
        in-trace expansion (see the section comment above)."""
        cf = self._fns.get(("count",))
        if cf is None:
            cf = self._build_count_fn()
            self._fns[("count",)] = cf
        lo, counts = _on_device("join_count", cf,
                                left_cols[self.join["left_key"]],
                                bkeys_pad, scalars, starts, np.int32(n),
                                iters=iters)
        prefix = np.zeros(len(counts) + 1, dtype=np.int64)
        np.cumsum(counts, dtype=np.int64, out=prefix[1:])
        total = int(prefix[-1])
        if total == 0:
            # Nothing matched: the interpreted tail is O(probe) and keeps
            # the empty-output schema semantics in one place.
            return self._numpy_tail(batch, build, ops_host)
        if total > _INT32_MAX:
            _warn_int32_fallback(
                f"duplicate-key expansion of {total} rows exceeds int32")
            return self._numpy_tail(batch, build, ops_host)

        n_out = total
        if n_out not in self._seen_out and \
                len(self._seen_out) >= _MAX_RAW_SHAPES:
            n_out = _pow2(n_out)
        self._seen_out.add(n_out)

        key = ("expand",) + schema_key
        ef = self._fns.get(key)
        if ef is None:
            ef = self._build_expand_fn(sources, left_in, right_in)
            self._fns[key] = ef
        res = _on_device("join_expand", ef, left_cols, lits_t, bpay_sorted,
                         lo, prefix.astype(np.int32), np.int32(total),
                         r=r, n_out=n_out)
        assign = res[0][:total]
        derived = res[1]
        lsel = res[2][:total]
        rpos = res[3][:total]

        order, counts_p = self._stable_partition(assign, r)
        out = self._gather_out(batch, bpay_out, sources_host, derived,
                               order, lsel[order], rpos[order], total)
        return self._emit(out, counts_p, r)


@functools.lru_cache(maxsize=256)
def _compile_tail(segment_json: str, partition) -> _FusedTail:
    return _FusedTail(json.loads(segment_json), partition)


def _strip_build(op: dict) -> dict:
    return {k: v for k, v in op.items() if k != "build"}


def _run_tail(batch: ColumnBatch, segment: list[dict], partition):
    build = segment[0].get("build") if segment and \
        segment[0]["op"] == "hash_join" else None
    canon, lits = _canon_json([_strip_build(op) for op in segment])
    tail = _compile_tail(canon, partition)
    return tail.run(batch, build, lits)


# ---------------------------------------------------------------------------
# hash_agg over the Pallas segmented reduction
# ---------------------------------------------------------------------------

def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


# Above this group cardinality the O(rows x groups) one-hot kernel loses
# to sort+reduceat; hash_agg falls back to the numpy reduction.
_MAX_KERNEL_GROUPS = 1024


def _dense_groups(batch: ColumnBatch, keys: list[str]):
    """Group rows by integer keys whose value ranges multiply to at most
    ``_MAX_KERNEL_GROUPS``, without a sort: the mixed-radix code of the
    keys (first key most significant) is a group's rank in the
    lexicographic order ``group_boundaries`` gives. Returns
    ``(ids, counts, key_values)``, with each row's group id in input
    order, or None where the keys are not such a domain."""
    cols = [np.asarray(batch[k]) for k in keys]
    if not all(c.dtype.kind in "iu" for c in cols):
        return None
    lows = [c.min() for c in cols]
    radices = [int(c.max()) - int(lo) + 1 for c, lo in zip(cols, lows)]
    size = math.prod(radices)
    if size > _MAX_KERNEL_GROUPS:
        return None
    code = np.zeros(batch.num_rows, dtype=np.int32)
    for c, lo, r in zip(cols, lows, radices):
        # c - lo wraps in c's dtype; read unsigned it is exact, since
        # every offset is below r <= 1024 and below 2**bits.
        code *= r
        code += (c - lo).view(f"u{c.itemsize}").astype(np.int32)
    present = np.bincount(code, minlength=size)
    groups = np.flatnonzero(present)
    remap = np.empty(size, dtype=np.int32)
    remap[groups] = np.arange(len(groups), dtype=np.int32)
    out = {}
    stride = size
    for k, c, lo, r in zip(keys, cols, lows, radices):
        stride //= r
        # The same wrap back: lo + offset lands on the key's own value.
        out[k] = ((groups // stride) % r).astype(c.dtype) + lo
    return np.take(remap, code), present[groups], out


def _reduce_on_device(batch: ColumnBatch, aggs: list[list],
                      ids: np.ndarray, n_groups: int, out: dict) -> None:
    """Reduce the sum, min and max aggregates of ``batch`` into
    ``n_groups`` groups by each row's group id (``ids``, in any row
    order) in the segmented-reduction kernel, into ``out``. Rows and
    segments pad to powers of two so jit/pallas shapes recur across
    fragments (padding rows carry segment id -1: reduced into nothing).
    Same-mode aggregates stack into one kernel launch."""
    n = len(ids)
    n_pad = _pow2(n)
    s_pad = _pow2(n_groups)
    ids_pad = np.full(n_pad, -1, dtype=np.int32)
    ids_pad[:n] = ids
    for mode in ("sum", "min", "max"):
        group = [(name, col) for name, fn, col in aggs if fn == mode]
        if not group:
            continue
        # Each column casts to float32 as it is written into the slab;
        # only the padding is zeroed.
        vals = np.empty((len(group), n_pad), dtype=np.float32)
        vals[:, n:] = 0
        for row, (_, col) in enumerate(group):
            vals[row, :n] = batch[col]
        # Called through the module's global name, which a caller may
        # wrap to count the kernel's work.
        red = _on_device("segment_reduce", segment_reduce, vals, ids_pad,
                         num_segments=s_pad, mode=mode,
                         interpret=tpu.interpret())
        for row, (name, _) in enumerate(group):
            out[name] = red[row, :n_groups].astype(np.float64)


def _run_hash_agg(batch: ColumnBatch, keys: list[str],
                  aggs: list[list]) -> ColumnBatch:
    if batch.num_rows == 0:
        return operators.op_hash_agg(batch, keys, aggs)
    n = batch.num_rows
    dense = _dense_groups(batch, keys) if keys else None
    if dense is not None:
        spans.count("hash_agg.dense")
        ids, counts, out = dense
    else:
        if keys:
            spans.count("hash_agg.sorted")
        order, starts, out = operators.group_boundaries(batch, keys)
        counts = np.diff(np.append(starts, n))
        if len(starts) > _MAX_KERNEL_GROUPS:
            # The one-hot kernel is O(rows x groups): past this
            # cardinality (e.g. bb_q3's per-item reduce) sort+reduceat
            # wins by orders of magnitude, so keep the kernel for the
            # low-cardinality TPC shape.
            ids = None
            for name, fn, col in aggs:
                if fn != "count":
                    vals = np.asarray(batch[col], dtype=np.float64)
                    out[name] = operators._AGG_FNS[fn](vals[order], starts)
        else:
            # The kernel reduces rows in any order: scatter each sorted
            # row's group id back to its input position in place of
            # gathering every value column through the sort.
            ids = np.empty(n, dtype=np.int32)
            ids[order] = np.repeat(np.arange(len(starts), dtype=np.int32),
                                   counts)
    if ids is not None:
        _reduce_on_device(batch, aggs, ids, len(counts), out)
    for name, fn, _ in aggs:
        if fn == "count":
            out[name] = counts.astype(np.int64)
    # Match the interpreted backend's column order: keys, then aggs.
    return ColumnBatch({name: out[name]
                        for name in list(keys) + [a[0] for a in aggs]})


# ---------------------------------------------------------------------------
# UDFs
# ---------------------------------------------------------------------------

def _fits(v: np.ndarray, below: int) -> bool:
    return v.size == 0 or (v.dtype.kind in "iu" and int(v.min()) >= 0
                           and int(v.max()) < below)


def _session_window_udf(batch: ColumnBatch, *, order_by, item_categories,
                        target_category, views=5, seconds=864000):
    """``operators.clicks_before_purchase`` through
    ``kernels.session_window``: the same rows, with one row per viewed
    item whose ``n`` is its count. None where a column does not fit the
    int32 program, or the declared order does not name all four
    columns."""
    names = tuple(order_by or ())
    if sorted(names) != sorted(sw_kernel.COLUMNS):
        return None
    cats = np.asarray(item_categories)
    cols = {c: np.asarray(batch[c]) for c in sw_kernel.COLUMNS}
    limit = np.iinfo(np.int32).max
    if not (_fits(cols[sw_kernel.USER], limit)
            and _fits(cols[sw_kernel.TSTAMP], limit)
            and _fits(cols[sw_kernel.ITEM], len(cats))
            and _fits(cols[sw_kernel.CTYPE], 127)
            and _fits(cats, limit) and 0 <= target_category < limit
            and 0 <= seconds < limit):
        return None
    n = batch.num_rows
    n_pad = _pow2(n)
    args = []
    for i, c in enumerate(names):
        v = cols[c].astype(np.int8 if c == sw_kernel.CTYPE else np.int32)
        # Padding rows sort last: the first key's largest value.
        fill = np.iinfo(v.dtype).max if i == 0 else 0
        args.append(np.concatenate([v, np.full(n_pad - n, fill, v.dtype)]))
    # Room for every purchase, of any category, so that the shape does
    # not depend on the target.
    room = _pow2(max(1, int(np.count_nonzero(
        cols[sw_kernel.CTYPE] == sw_kernel.PURCHASE))))
    counts, purchases = _on_device(
        "session_window", sw_kernel.session_window, tuple(args),
        np.int32(n), cats.astype(np.int32), np.int32(target_category),
        np.int32(seconds), names=names, views=views, max_purchases=room)
    spans.count("session_window.calls")
    spans.count("session_window.rows", n)
    spans.count("session_window.purchases", int(purchases))
    viewed = np.flatnonzero(counts)
    return {"viewed_item": viewed.astype(np.int64),
            "n": counts[viewed].astype(np.int64)}


# UDFs with a traced implementation, by name: ``fn(batch, order_by=...,
# **kwargs)`` returns the UDF's output columns, or None where the batch
# does not fit its program (the numpy UDF then runs instead).
_TRACED_UDFS = {"clicks_before_purchase": _session_window_udf}


def _run_udf(batch: ColumnBatch, op: dict) -> ColumnBatch:
    """A ``udf`` op: the UDF's traced implementation where
    ``_TRACED_UDFS`` has one and the batch fits its int32 program, else
    its numpy function; either way inside one ``engine.udf`` span, the
    device call nested in it."""
    name, kwargs = op["name"], op.get("kwargs", {})
    order_by = op.get("order_by")
    traced = _TRACED_UDFS.get(name)
    with span("engine.udf", udf=name, rows_in=batch.num_rows) as sp:
        out = None
        if traced is not None and batch.num_rows:
            out = traced(batch, order_by=order_by, **kwargs)
            if out is None:
                FALLBACK_STATS["int32_udf"] += 1
        out = operators.udf_reference(batch, name, order_by, **kwargs) \
            if out is None else ColumnBatch(out)
        sp.attrs["rows_out"] = out.num_rows
    return out


# ---------------------------------------------------------------------------
# Pipeline driver
# ---------------------------------------------------------------------------

@spanned("engine.ops")
def run_pipeline_jit(batch: ColumnBatch, ops: list[dict]) -> ColumnBatch:
    """Execute a pipeline spec with the compiled backend. Result-compatible
    with ``operators.run_pipeline_ops`` (modulo float32 accumulation)."""
    i = 0
    while i < len(ops):
        kind = ops[i]["op"]
        if kind in ("filter", "project"):
            j = i
            while j < len(ops) and ops[j]["op"] in ("filter", "project"):
                j += 1
            batch = _run_fused(batch, ops[i:j])
            i = j
        elif kind == "hash_join":
            # The join and every following filter/project trace together:
            # predicates AND into the probe's match mask, so the join
            # output compacts once, after all of them.
            j = i + 1
            while j < len(ops) and ops[j]["op"] in ("filter", "project"):
                j += 1
            batch = _run_tail(batch, ops[i:j], None)
            i = j
        elif kind == "hash_agg":
            batch = _run_hash_agg(batch, ops[i]["keys"], ops[i]["aggs"])
            i += 1
        elif kind == "udf":
            batch = _run_udf(batch, ops[i])
            i += 1
        else:
            raise ValueError(f"unknown operator {kind!r}")
    return batch


# Ops whose output over a concatenation of morsels equals the
# concatenation of their per-morsel outputs, bit for bit: filters and
# projections are row-local, and a hash-join probe depends only on the
# (whole) build side, emitting matches in probe order. Aggregates and
# UDFs are barriers — they need the full fragment.
STREAMABLE_OPS = ("filter", "project", "hash_join")


def streamable_prefix(ops: list[dict]) -> int:
    """Number of leading ops safe to evaluate morsel-at-a-time with
    bit-identical concatenated output (see ``STREAMABLE_OPS``). The
    out-of-core worker streams this prefix and accumulates (spilling
    under memory pressure) before the first barrier op."""
    for i, op in enumerate(ops):
        if op["op"] not in STREAMABLE_OPS:
            return i
    return len(ops)


def _fusable_tail_start(ops: list[dict]) -> int:
    """Index where the trailing ``[hash_join?] + (filter|project)*`` run
    begins (``len(ops)`` when the pipeline ends in an agg/udf)."""
    t = len(ops)
    while t > 0 and ops[t - 1]["op"] in ("filter", "project"):
        t -= 1
    if t > 0 and ops[t - 1]["op"] == "hash_join":
        t -= 1
    return t


@spanned("engine.ops")
def run_pipeline_partition(batch: ColumnBatch, ops: list[dict],
                           key_col: str, partitions: int,
                           backend: str = "numpy") -> list[ColumnBatch]:
    """Execute a pipeline spec and radix-partition its output for a
    shuffle write, returning ``partitions`` contiguous ColumnBatches.

    On the jit backend the trailing ``[hash_join?] + (filter|project)*``
    run and the partition assignment compile into one traced call (see
    ``_FusedTail``); the numpy backend is the interpreted reference:
    ``run_pipeline_ops`` + ``operators.radix_partition``.

    A trailing ``hash_agg`` partitioned by one of its own group keys —
    the optimizer's partial pre-agg shuffle shape — no longer splits the
    trace: partitioning by a group key commutes with the per-fragment
    aggregation, so the segment BEFORE the agg fuses with the partition
    assignment into one traced call and the aggregation runs per
    partition slice. The stable partition preserves each group's row
    order, so the per-slice aggregation sees the same values in the
    same order as aggregating first; the pairwise sum tree's
    association can still shift with a group's offset inside the kernel
    block, so float sums may differ from agg-then-partition in the last
    ulp — well inside the backend's rtol=1e-6 contract, but not
    bit-identical.
    """
    if backend == "numpy":
        return operators.radix_partition(
            operators.run_pipeline_ops(batch, ops), key_col, partitions)
    if backend != "jit":
        raise ValueError(f"unknown backend {backend!r}")
    t = _fusable_tail_start(ops)
    if t == len(ops) and ops and ops[-1]["op"] == "hash_agg" \
            and key_col in ops[-1]["keys"]:
        s = _fusable_tail_start(ops[:-1])
        seg = ops[s:-1]
        if seg:   # something to fuse the assignment into
            agg = ops[-1]
            batch = run_pipeline_jit(batch, ops[:s])
            parts = _run_tail(batch, seg, (key_col, partitions))
            return [_run_hash_agg(p, agg["keys"], agg["aggs"])
                    for p in parts]
    batch = run_pipeline_jit(batch, ops[:t])
    if t == len(ops):
        return operators.radix_partition(batch, key_col, partitions)
    return _run_tail(batch, ops[t:], (key_col, partitions))


BACKENDS = {
    "numpy": operators.run_pipeline_ops,
    "jit": run_pipeline_jit,
}


def run_pipeline(batch: ColumnBatch, ops: list[dict],
                 backend: str = "numpy") -> ColumnBatch:
    try:
        fn = BACKENDS[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}") from None
    return fn(batch, ops)


@spanned("engine.ops")
def run_pipeline_collect(batch: ColumnBatch, ops: list[dict],
                         backend: str = "numpy") -> ColumnBatch:
    """Execute a COLLECT fragment's pipeline spec.

    Same results as ``run_pipeline``, but on the jit backend a trailing
    keyed ``hash_agg`` — the optimizer's collapsed partial+final
    aggregate after a combine-shuffle elision — fuses with its preceding
    ``[hash_join?] + (filter|project)*`` segment through the
    ``_FusedTail`` machinery at a single partition: the join probe, the
    fused predicate mask, the derived projections and the live-row
    compaction run as ONE traced call (exactly like the shuffle
    fragment's partition fusion, with r=1), then the aggregation runs
    over the compacted slice. Integer group keys only; other shapes fall
    through to the plain drivers unchanged.
    """
    if backend == "jit" and ops and ops[-1]["op"] == "hash_agg" \
            and ops[-1]["keys"]:
        agg = ops[-1]
        s = _fusable_tail_start(ops[:-1])
        seg = ops[s:-1]
        key0 = agg["keys"][0]
        # Only take the fused path when the partition key will trace as
        # an integer — a float group key would push the WHOLE segment
        # onto the interpreted fallback inside _FusedTail.
        key_is_int = key0 not in batch \
            or np.asarray(batch[key0]).dtype.kind in "iu"
        if seg and key_is_int:
            head = run_pipeline_jit(batch, ops[:s])
            parts = _run_tail(head, seg, (key0, 1))
            return _run_hash_agg(parts[0], agg["keys"], agg["aggs"])
    return run_pipeline(batch, ops, backend=backend)

# ---------------------------------------------------------------------------
# Query-level compiled-plan cache
# ---------------------------------------------------------------------------

class CompiledPlanCache:
    """Query-level view of the compiled-plan cache.

    The operative trace sharing lives in the canonical-keyed lru caches
    above (``_compile_segment`` / ``_compile_tail``): two plans with the
    same ``plans.plan_shape_hash`` hand those caches identical keys, so a
    plan-level hit means every traced object the query's fragments will
    look up is already resident (modulo lru eviction, which only costs a
    retrace). This class keys that property by shape hash — an LRU of the
    shapes whose compiled callables have been materialized — and exposes
    the hit/miss counters that serving metrics and ``explain`` report.

    Literal values are NOT part of the key (they travel as traced
    arguments); tables are keyed positionally, so a same-shape query over
    different tables also hits. ``maxsize`` bounds remembered shapes, not
    traces — the trace caches have their own bound.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def lookup(self, plan) -> tuple[str, bool]:
        """Record a query against the cache. Returns ``(shape_hash,
        hit)``; on a miss the shape is inserted so the next same-shape
        query hits."""
        key = engine_plans.plan_shape_hash(plan)
        if key in self._entries:
            self._entries.move_to_end(key)
            self.hits += 1
            return key, True
        self.misses += 1
        self._entries[key] = plan.name
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
        return key, False

    def contains(self, shape_hash: str) -> bool:
        return shape_hash in self._entries

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._entries)}

    def clear(self) -> None:
        self._entries.clear()
        self.hits = 0
        self.misses = 0


# Process-wide instance used by ``Coordinator.execute`` (the trace caches
# it fronts are process-wide too).
PLAN_CACHE = CompiledPlanCache()
