"""Recursive jaxpr traversal — the single source of truth for walking
trust-kernel jaxprs.

Grown out of the ad-hoc ``_collect_gathers`` helper that used to live in
``tests/test_windowed_pipeline.py``: every consumer (the invariant
analyzer, the gather-counting acceptance test) now shares one walker,
so "descends into pjit / while / scan / shard_map / pallas interpret
bodies" cannot drift between the test and the gate.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Iterator

import jax

#: Primitive families the invariant checks care about.
SCATTER_PRIMITIVES = frozenset(
    {"scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max"}
)
#: ``psum_invariant`` is what ``psum`` traces to inside a
#: ``shard_map`` with its varying-axes check on — the same collective
#: on the wire.
PSUM_PRIMITIVES = frozenset({"psum", "psum_invariant"})
CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"}
)


@dataclass(frozen=True)
class EqnSite:
    """One equation plus the primitive path enclosing it (outermost
    first) — e.g. ``("pjit", "while", "shard_map")``."""

    eqn: Any  # jax.core.JaxprEqn
    path: tuple[str, ...]

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    def under(self, primitive: str) -> bool:
        return primitive in self.path

    @property
    def out_shape(self) -> tuple[int, ...]:
        return tuple(self.eqn.outvars[0].aval.shape)

    @property
    def sorted_indices(self) -> bool:
        return bool(self.eqn.params.get("indices_are_sorted"))

    @property
    def unique_indices(self) -> bool:
        return bool(self.eqn.params.get("unique_indices"))


def _is_jaxpr_like(x: Any) -> bool:
    return hasattr(x, "eqns") or hasattr(x, "jaxpr")


def iter_eqns(jaxpr: Any, path: tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Yield every equation of ``jaxpr`` and, recursively, of every
    sub-jaxpr reachable through equation params (pjit bodies, while
    cond/body, scan bodies, shard_map bodies, pallas interpret
    kernels), tagged with the enclosing primitive path."""
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, path)
        sub_path = path + (eqn.primitive.name,)
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(v, is_leaf=_is_jaxpr_like):
                if hasattr(sub, "jaxpr"):  # ClosedJaxpr
                    yield from iter_eqns(sub.jaxpr, sub_path)
                elif hasattr(sub, "eqns"):  # raw Jaxpr
                    yield from iter_eqns(sub, sub_path)


def collect_primitives(
    jaxpr: Any,
    names: frozenset[str] | set[str],
    *,
    exclude_under: tuple[str, ...] = (),
    predicate: Callable[[EqnSite], bool] | None = None,
) -> list[EqnSite]:
    """All equation sites whose primitive is in ``names``, skipping
    sites nested under any primitive named in ``exclude_under``."""
    out = []
    for site in iter_eqns(jaxpr):
        if site.primitive not in names:
            continue
        if any(site.under(p) for p in exclude_under):
            continue
        if predicate is not None and not predicate(site):
            continue
        out.append(site)
    return out


def collect_gathers(jaxpr: Any, *, exclude_pallas: bool = False) -> list[Any]:
    """Every ``gather`` equation, descending into sub-jaxprs — the
    (generalized) successor of the test-local ``_collect_gathers``.
    Returns bare equations for drop-in use by shape/param assertions;
    ``exclude_pallas`` drops gathers inside interpret-mode
    ``pallas_call`` bodies (not XLA gathers on the real chip)."""
    exclude = ("pallas_call",) if exclude_pallas else ()
    return [s.eqn for s in collect_primitives(jaxpr, {"gather"}, exclude_under=exclude)]


def primitive_counts(jaxpr: Any) -> dict[str, int]:
    """Histogram of primitive names over the whole (recursive) jaxpr."""
    counts: dict[str, int] = {}
    for site in iter_eqns(jaxpr):
        counts[site.primitive] = counts.get(site.primitive, 0) + 1
    return counts


def source_site(eqn: Any) -> tuple[str | None, int | None]:
    """Best-effort ``(file, line)`` of the user code that traced this
    equation (jaxpr source_info; internal frames filtered by jax)."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None, None
    return frame.file_name, frame.start_line


def has_f64(jaxpr: Any) -> list[EqnSite]:
    """Equation sites producing a float64 aval anywhere in the jaxpr —
    device f64 is emulated on TPU and must never appear in a hot
    kernel (the double-single (hi, lo) machinery exists precisely to
    avoid it)."""
    out = []
    for site in iter_eqns(jaxpr):
        for v in site.eqn.outvars:
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                out.append(site)
                break
    return out


__all__ = [
    "CALLBACK_PRIMITIVES",
    "EqnSite",
    "PSUM_PRIMITIVES",
    "SCATTER_PRIMITIVES",
    "collect_gathers",
    "collect_primitives",
    "has_f64",
    "iter_eqns",
    "primitive_counts",
    "source_site",
]
