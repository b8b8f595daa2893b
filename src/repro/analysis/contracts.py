"""Trace-level contract checks: jaxpr-hash recompile stability, dtype
hygiene, host-sync freedom, donation — plus the shared dispatch-count
assertions the benchmark self-checks call.

The checks operate on **abstract avals only**: every entry point is traced
with ``jax.make_jaxpr`` over ``ShapeDtypeStruct``/host-array arguments, so
proving e.g. that the fused curve engine never retraces across perturbed
``p_miss`` leaves costs two traces and zero device executions — no training
step, no serve tick, no kernel launch.

Rules implemented here (see ``repro.analysis.registry`` for what each entry
point declares):

``recompile-hazard``
    Rebinding the contract's traced leaves (the protocol's ``p_miss``) must
    neither change the argument treedef (a static/meta leaf would) nor the
    canonicalized jaxpr hash (a host-materialized value baked into the
    trace would).  Tracing that *raises* a concretization error is the same
    hazard reported with the trace error attached.

``f64-promotion``
    The entry point is re-traced under ``jax.enable_x64`` and
    the jaxpr is walked for float64/complex128 *array* avals (scalar weak-
    type f64 intermediates are JAX-internal promotion noise and stay
    legal) and for ``convert_element_type`` ops landing on f64 arrays.
    Code with explicit dtypes everywhere — the repo convention — traces
    identically with and without x64, so this proves an ``JAX_ENABLE_X64``
    host cannot silently double the engines' memory traffic.

``host-sync``
    No callback primitive (``pure_callback``/``io_callback``/
    ``debug_callback``) anywhere in the jaxpr, except an explicit
    per-contract allowlist: callbacks stall the dispatch pipeline on a
    host round-trip.

``donation-alias``
    Arguments the contract declares donated must actually lower as donated
    buffers (``tf.aliasing_output``/``jax.buffer_donor`` input attributes
    in the lowered module); ``repro.analysis.hlo_checks`` additionally
    asserts the compiled executable aliases them (``input_output_alias``).
"""

from __future__ import annotations

import hashlib
import math
import re
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

import jax
import numpy as np

from repro.analysis import report as R
from repro.analysis.report import Finding

try:  # jax >= 0.4.36 moved the IR types to jax.extend.core
    from jax.extend import core as jcore
except ImportError:  # pragma: no cover - older jax
    from jax import core as jcore

CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"})

_ADDR_RE = re.compile(r"0x[0-9a-f]+")
_F64 = (np.dtype(np.float64), np.dtype(np.complex128))


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------

def _jaxpr_of(x):
    """The raw ``Jaxpr`` behind a ``ClosedJaxpr``/``Jaxpr`` value."""
    return x.jaxpr if hasattr(x, "jaxpr") else x


def iter_jaxprs(closed) -> Iterable:
    """The jaxpr and every sub-jaxpr reachable through eqn params
    (pjit bodies, scan/while carries, cond/switch branches, custom_vjp
    calls, ...), depth-first."""
    seen = []
    stack = [_jaxpr_of(closed)]
    while stack:
        j = stack.pop()
        seen.append(j)
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    stack.append(sub)


def _sub_jaxprs(param) -> List:
    if isinstance(param, (jcore.Jaxpr, jcore.ClosedJaxpr)):
        return [_jaxpr_of(param)]
    if isinstance(param, (list, tuple)):
        out = []
        for p in param:
            out.extend(_sub_jaxprs(p))
        return out
    return []


def iter_eqns(closed) -> Iterable:
    for j in iter_jaxprs(closed):
        for eqn in j.eqns:
            yield eqn


def canonical_jaxpr(closed) -> str:
    """Deterministic jaxpr text: object addresses (callback closures,
    custom_vjp bwd thunks) are scrubbed so two traces of the same program
    hash equal."""
    return _ADDR_RE.sub("0x", str(closed))


def jaxpr_hash(closed) -> str:
    return hashlib.sha256(canonical_jaxpr(closed).encode()).hexdigest()


def _aval_of(var):
    return getattr(var, "aval", None)


def _leaf_aval(x):
    x = np.asarray(x) if not hasattr(x, "shape") else x
    return (tuple(x.shape), np.dtype(x.dtype))


# ---------------------------------------------------------------------------
# the individual checks
# ---------------------------------------------------------------------------

def check_trace_stable(name: str, fn: Callable,
                       argsf: Callable[[float], Tuple],
                       perturb: Sequence[float] = (0.03, 0.11),
                       ) -> List[Finding]:
    """The jaxpr-hash recompile check: ``fn(*argsf(p))`` must trace to the
    same program for every perturbation ``p`` of the rebindable leaves."""
    where = f"contract:{name}"

    def _trace(args):
        # a fresh wrapper per trace defeats jax's tracing cache (keyed on
        # fn identity + avals) — the cache would replay the FIRST trace and
        # mask host values baked in through closures, the exact hazard this
        # check exists to catch
        return jax.make_jaxpr(lambda *a: fn(*a))(*args)

    base, rest = perturb[0], perturb[1:]
    args0 = argsf(base)
    leaves0, tree0 = jax.tree_util.tree_flatten(args0)
    try:
        h0 = jaxpr_hash(_trace(args0))
    except Exception as e:  # concretization of the traced leaf, usually
        return [Finding(
            R.RECOMPILE_HAZARD, where, "trace-error",
            f"tracing with perturbed leaf={base} raised "
            f"{type(e).__name__}: {e}")]
    findings: List[Finding] = []
    for p in rest:
        args1 = argsf(p)
        leaves1, tree1 = jax.tree_util.tree_flatten(args1)
        if tree1 != tree0:
            findings.append(Finding(
                R.RECOMPILE_HAZARD, where, "treedef",
                f"rebinding the traced leaf to {p} changes the argument "
                f"treedef — the leaf is static metadata, every rebind "
                f"retraces"))
            continue
        mismatch = [i for i, (a, b) in enumerate(zip(leaves0, leaves1))
                    if _leaf_aval(a) != _leaf_aval(b)]
        if mismatch:
            findings.append(Finding(
                R.RECOMPILE_HAZARD, where, "aval",
                f"rebinding the traced leaf to {p} changes argument avals "
                f"at flat positions {mismatch} — shape/dtype-unstable "
                f"rebinds retrace"))
            continue
        try:
            h1 = jaxpr_hash(_trace(args1))
        except Exception as e:
            findings.append(Finding(
                R.RECOMPILE_HAZARD, where, "trace-error",
                f"tracing with perturbed leaf={p} raised "
                f"{type(e).__name__}: {e}"))
            continue
        if h1 != h0:
            findings.append(Finding(
                R.RECOMPILE_HAZARD, where, "jaxpr-hash",
                f"jaxpr hash changes when the traced leaf rebinds "
                f"{base} -> {p}: a leaf value is baked into the trace "
                f"(host materialization or static capture)"))
    return findings


def check_no_host_sync(name: str, fn: Callable, args: Tuple,
                       allowlist: Sequence[str] = ()) -> List[Finding]:
    """No callback primitives anywhere in the traced program."""
    where = f"contract:{name}"
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:
        return [_trace_error(name, "host-sync", e)]
    findings = []
    seen = set()
    for eqn in iter_eqns(closed):
        pname = eqn.primitive.name
        if pname in CALLBACK_PRIMITIVES and pname not in allowlist \
                and pname not in seen:
            seen.add(pname)
            findings.append(Finding(
                R.HOST_SYNC, where, pname,
                f"jitted program contains a `{pname}` primitive — a host "
                f"round-trip inside the dispatch (allowlist it in the "
                f"contract if intentional)"))
    return findings


def check_no_f64(name: str, fn: Callable,
                 argsf: Callable[[float], Tuple]) -> List[Finding]:
    """Trace under enable_x64 and walk for f64 *array* avals.

    Entry points with explicit dtypes everywhere are x64-invariant; an
    untyped ``jnp.zeros``/``jnp.asarray``/np-f64 constant shows up here as
    an f64 array the moment someone runs with ``JAX_ENABLE_X64=1``.
    """
    where = f"contract:{name}"
    from jax import enable_x64
    args = argsf(0.05)
    try:
        with enable_x64():
            closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:
        # the plain trace succeeds (the recompile/host-sync checks ran), so
        # failing only under x64 is itself the dtype instability
        return [Finding(
            R.F64_PROMOTION, where, "x64-trace",
            f"entry point fails to trace under JAX_ENABLE_X64 "
            f"({type(e).__name__}: {e}) — an unpinned dtype promotes and "
            f"collides; pin dtypes explicitly")]
    findings: List[Finding] = []
    seen = set()

    def flag(detail: str, msg: str):
        if detail not in seen:
            seen.add(detail)
            findings.append(Finding(R.F64_PROMOTION, where, detail, msg))

    for eqn in iter_eqns(closed):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = _aval_of(var)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            try:
                dt = np.dtype(aval.dtype)
            except TypeError:       # extended dtypes (typed PRNG keys)
                continue
            if dt in _F64 and getattr(aval, "ndim", 0) >= 1:
                flag(f"{eqn.primitive.name}:{dt.name}",
                     f"`{eqn.primitive.name}` touches a "
                     f"{dt.name}{list(aval.shape)} array "
                     f"under JAX_ENABLE_X64 — an untyped construction "
                     f"silently promotes (pin the dtype explicitly)")
    return findings


def check_donation(name: str, jitted: Callable, args: Tuple,
                   n_expected: int) -> List[Finding]:
    """Declared donated arguments must lower as donated buffers."""
    where = f"contract:{name}"
    try:
        text = jitted.lower(*args).as_text()
    except Exception as e:
        return [_trace_error(name, "donation", e)]
    donated = text.count("tf.aliasing_output") + text.count("jax.buffer_donor")
    if donated < n_expected:
        return [Finding(
            R.DONATION_ALIAS, where, "lowered",
            f"contract declares {n_expected} donated buffers but only "
            f"{donated} lower with a donation attribute "
            f"(tf.aliasing_output/jax.buffer_donor) — donate_argnums "
            f"dropped or shapes no longer alias")]
    return []


def _trace_error(name: str, what: str, e: Exception) -> Finding:
    return Finding(
        R.CHECK_ERROR, f"contract:{name}", what,
        f"{what} check could not trace the entry point: "
        f"{type(e).__name__}: {e}")


# ---------------------------------------------------------------------------
# shared dispatch-count assertions (the bench self-checks call these; the
# registry documents each entry point's declared bound)
# ---------------------------------------------------------------------------

def fused_dispatch_bound(steps: int, log_every: int) -> int:
    """Host dispatches one fused curve run may cost per ``bits`` value:
    the single fused dispatch plus the logged-buffer fetches."""
    return math.ceil(steps / log_every) + 2


def assert_trace_count(observed: int, expected: int, what: str) -> None:
    """Exactly-N-compilations contract (e.g. one per ``bits`` value)."""
    if observed != expected:
        raise RuntimeError(
            f"{what} recompiled: {observed} traces, expected {expected} — "
            "a traced leaf regressed to static (zero-recompile contract)")


def assert_fused_dispatches(dispatches_per_bits: float, steps: int,
                            log_every: int) -> None:
    """The fused curve engine's one-dispatch contract (per ``bits``)."""
    bound = fused_dispatch_bound(steps, log_every)
    if dispatches_per_bits > bound:
        raise RuntimeError(
            f"fused engine dispatched {dispatches_per_bits}/bits — exceeds "
            f"the ceil(steps/log_every)+2 = {bound} fusion bound")


def assert_single_dispatch(counts: Dict[str, int], key: str,
                           what: str) -> None:
    """Whole-run-in-ONE-dispatch contract (the scheduled curve engine)."""
    if counts.get(key) != 1:
        raise RuntimeError(
            f"{what} cost {counts} dispatches — must fuse to ONE")


def assert_tick_dispatch_bracket(name: str, decode_tokens: int, ticks: int,
                                 batch_slots: int) -> None:
    """One fused dispatch per serve decode tick.

    Every dispatch decodes >=1 active slot (the engine never dispatches an
    empty batch) and <= batch_slots tokens, so the counted dispatches must
    bracket the total decoded-token count: extra per-tick host->device hops
    push the count above the token total, skipped fusions below tokens/B.
    """
    lo = -(-decode_tokens // batch_slots)            # ceil division
    if not lo <= ticks <= decode_tokens:
        raise RuntimeError(
            f"{name}: {ticks} decode dispatches for {decode_tokens} decoded "
            f"tokens over {batch_slots} slots — not one fused dispatch per "
            f"tick (expected in [{lo}, {decode_tokens}])")
