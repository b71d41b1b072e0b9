"""Small jaxpr-inspection helpers shared by tests and benchmarks."""
from __future__ import annotations


def _subjaxprs(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        # e.g. lax.cond/switch store their branches as a tuple of jaxprs
        for item in v:
            yield from _subjaxprs(item)


def _walk(jaxpr, visit) -> int:
    count = 0
    for eqn in jaxpr.eqns:
        count += visit(eqn)
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                count += _walk(sub, visit)
    return count


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` anywhere in ``jaxpr``
    (recursing into sub-jaxprs)."""
    return _walk(jaxpr, lambda eqn: eqn.primitive.name == name)


def count_pallas_calls(jaxpr) -> int:
    """Number of ``pallas_call`` primitives anywhere in ``jaxpr``
    (recursing into sub-jaxprs) — i.e. kernel dispatches per trace."""
    return count_primitive(jaxpr, "pallas_call")


def count_eqns(jaxpr) -> int:
    """Total equation count including sub-jaxprs — a dispatch/step-count
    proxy for comparing fused vs unfused lowerings."""
    return _walk(jaxpr, lambda eqn: 1)


def pallas_grid_steps(jaxpr) -> int:
    """Total static grid steps across every ``pallas_call`` in
    ``jaxpr`` (recursing into sub-jaxprs): the sum over dispatches of
    the product of their grid dims.

    This is the "grid work" a lowering commits to at trace time — the
    banded attention kernels shrink it when a static window (or static
    valid length) proves KV blocks masked, so benchmarks/tests can
    assert skipped blocks really left the grid rather than being
    masked in-kernel.
    """
    def visit(eqn):
        if eqn.primitive.name != "pallas_call":
            return 0
        steps = 1
        for dim in eqn.params["grid_mapping"].grid:
            steps *= int(dim)
        return steps

    return _walk(jaxpr, visit)
