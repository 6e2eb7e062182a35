"""Jaxpr auditor: trace registered hot paths, walk the jaxpr, flag bans.

The trace lint (:mod:`repro.analysis.trace_lint`) sees *source*; this
auditor sees what jax actually traced — so it catches violations the AST
cannot (a host callback buried three closure layers deep, an f64
intermediate introduced by dtype promotion rules, a structural difference
between two shapes of the same RHS bucket).

Four rules over each entry of :data:`repro.analysis.registry.HOT_ENTRIES`:

``jaxpr-host-callback``
    any callback-family primitive (``debug_print`` from
    ``jax.debug.print``, ``debug_callback``, ``pure_callback``, ``io_callback``,
    ``infeed``/``outfeed``) anywhere in the traced closure — each one is
    a device->host round trip per invocation.

``jaxpr-while-transfer``
    the same primitives *inside a ``while_loop`` body or cond* — a sync
    per PCG iteration, the catastrophic variant.

``jaxpr-f64-promotion``
    ``convert_element_type`` to float64, or any f64-dtyped intermediate,
    inside a declared-f32 entry.  Traced under ``jax.enable_x64``: with
    x64 disabled jax silently *downgrades* f64 requests, which would mask
    exactly the promotions we hunt.

``jaxpr-recompile-hazard``
    the entry traced at two shapes in the same RHS pow2 bucket (k=5 and
    k=7 -> bucket 8) must produce an identical primitive structure —
    otherwise the service's warmup-per-bucket compile amortization breaks
    (every new k inside a bucket would recompile).

Findings are located by the primitive's user source frame when jax
records one, falling back to the registry entry's name.
"""
from __future__ import annotations

import os
from typing import Iterable, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding
from repro.analysis.registry import HOT_ENTRIES, HotEntry

_CALLBACK_PRIMS = {
    "debug_print", "debug_callback", "pure_callback", "io_callback", "callback",
    "host_callback_call", "outside_call", "infeed", "outfeed",
}

# primitives whose params hold sub-jaxprs we must recurse into; everything
# is discovered generically from eqn.params, these are only for while-body
# special-casing
_WHILE_PRIM = "while"   # compared case-insensitively: jax names it "While"


def _sub_jaxprs(params: dict):
    """Yield every (Closed)Jaxpr reachable from an eqn's params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def walk(obj):
        if isinstance(obj, ClosedJaxpr):
            yield obj.jaxpr
        elif isinstance(obj, Jaxpr):
            yield obj
        elif isinstance(obj, (list, tuple)):
            for item in obj:
                yield from walk(item)
        elif isinstance(obj, dict):
            for item in obj.values():
                yield from walk(item)

    for value in params.values():
        yield from walk(value)


def _source_loc(eqn, default_file: str) -> Tuple[str, int]:
    """Best-effort (file, line) of the eqn's user frame."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            fname = frame.file_name
            # report repo-relative paths when the frame is ours
            for marker in ("src/repro/", "repro/"):
                k = fname.find(marker)
                if k >= 0:
                    fname = "src/repro/" + fname[k + len(marker):] \
                        if marker == "repro/" else fname[k:]
                    break
            return fname, frame.start_line
    except Exception:
        pass
    return default_file, 1


def _walk(jaxpr, in_while: bool):
    """Yield ``(eqn, in_while)`` over the jaxpr and all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, in_while
        inner_while = in_while or eqn.primitive.name.lower() == _WHILE_PRIM
        for sub in _sub_jaxprs(eqn.params):
            yield from _walk(sub, inner_while)


def _prim_structure(jaxpr) -> Tuple[str, ...]:
    """Flattened primitive-name sequence — the recompile-hazard
    comparison key.  Shapes/consts are deliberately excluded: two shapes
    of one bucket differ in constants but must agree here."""
    out: List[str] = []
    for eqn, _ in _walk(jaxpr, False):
        out.append(eqn.primitive.name)
    return tuple(out)


def _trace(fn, args, static_argnums: Tuple[int, ...]):
    import jax
    # x64 ON while tracing: with x64 off, jax silently downgrades f64 and
    # the promotion rule would never fire.  Entries are built f32, so a
    # clean path stays f32 under either flag.
    with jax.enable_x64(True):
        return jax.make_jaxpr(fn, static_argnums=static_argnums)(*args)


def audit_entry(entry: HotEntry) -> List[Finding]:
    """Run all four jaxpr rules over one registered entry."""
    import numpy as np

    findings: List[Finding] = []
    default_file = f"<registry:{entry.name}>"
    try:
        fn, args_small, args_sibling, static = entry.build()
        closed = _trace(fn, args_small, static)
    except Exception as e:  # building/tracing failed: that IS a finding
        return [Finding(
            file=default_file, line=1, rule="jaxpr-recompile-hazard",
            message=f"entry {entry.name} failed to build/trace: "
                    f"{type(e).__name__}: {e}")]

    jaxpr = closed.jaxpr
    f64 = np.dtype("float64")
    seen_lines = set()
    for eqn, in_while in _walk(jaxpr, False):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS:
            f, line = _source_loc(eqn, default_file)
            rule = "jaxpr-while-transfer" if in_while \
                else "jaxpr-host-callback"
            findings.append(Finding(
                file=f, line=line, rule=rule,
                message=f"primitive '{name}' in hot path "
                        f"'{entry.name}'"
                        + (" inside a while_loop body — one host sync "
                           "per PCG iteration" if in_while else
                           " — a device->host round trip per call")))
            continue
        if entry.declared_dtype == "float32":
            promo = (name == "convert_element_type"
                     and np.dtype(eqn.params.get("new_dtype")) == f64)
            wide_out = any(
                getattr(getattr(v, "aval", None), "dtype", None) == f64
                for v in eqn.outvars)
            if promo or wide_out:
                f, line = _source_loc(eqn, default_file)
                if (f, line, name) in seen_lines:
                    continue  # one finding per site, not per intermediate
                seen_lines.add((f, line, name))
                findings.append(Finding(
                    file=f, line=line, rule="jaxpr-f64-promotion",
                    message=f"'{name}' produces float64 inside "
                            f"declared-f32 hot path '{entry.name}' — "
                            f"f64 belongs only in the iterative-"
                            f"refinement wrapper outside the jit region"))

    if args_sibling is not None:
        try:
            sibling = _trace(fn, args_sibling, static)
        except Exception as e:
            findings.append(Finding(
                file=default_file, line=1, rule="jaxpr-recompile-hazard",
                message=f"entry {entry.name} failed to trace at the "
                        f"sibling bucket shape: {type(e).__name__}: {e}"))
        else:
            a = _prim_structure(jaxpr)
            b = _prim_structure(sibling.jaxpr)
            if a != b:
                k = next((i for i, (x, y) in enumerate(zip(a, b))
                          if x != y), min(len(a), len(b)))
                findings.append(Finding(
                    file=default_file, line=1,
                    rule="jaxpr-recompile-hazard",
                    message=f"jaxpr structure differs between two shapes "
                            f"of one RHS bucket for '{entry.name}' "
                            f"({len(a)} vs {len(b)} primitives, first "
                            f"divergence at #{k}: "
                            f"{a[k] if k < len(a) else '<end>'} vs "
                            f"{b[k] if k < len(b) else '<end>'}) — "
                            f"warmup-per-bucket amortization is broken"))
    return findings


def check_registry(entries: Optional[Sequence[HotEntry]] = None
                   ) -> List[Finding]:
    """Audit every registered hot entry (or an explicit subset)."""
    out: List[Finding] = []
    for entry in (HOT_ENTRIES if entries is None else entries):
        out.extend(audit_entry(entry))
    return out
