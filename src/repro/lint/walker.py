"""Shared jaxpr IR walker: ONE recursion over nested jax programs.

Every structural assertion this repo makes about its lowered programs —
"the merged program has no Q matmul", "paged prefill allocates no
max_len-sized buffer", "the step loop hides no host callback" — needs the
same traversal: walk a (closed) jaxpr's equations and recurse into every
inner jaxpr a higher-order primitive carries in its params (``scan``
bodies, ``cond`` branches, ``pjit``/``custom_vjp`` calls, ``pallas_call``
kernel bodies, …).  PR 3 and PR 4 each hand-wrote that recursion inside a
test; this module is the single copy the rule framework (and those tests)
walk with.

The traversal treats ANY ``Jaxpr``/``ClosedJaxpr`` leaf found in
an equation's params as an inner program — it doesn't enumerate primitive
names, so new higher-order primitives are covered automatically.
"""
from __future__ import annotations

from typing import Any, Iterator, List, Optional, Tuple

import jax
from jax.extend import core as jcore


def as_jaxpr(program) -> jcore.Jaxpr:
    """Accept a ``Jaxpr``, a ``ClosedJaxpr``, or anything carrying a
    ``.jaxpr`` attribute (``jax.make_jaxpr`` output) and return the bare
    ``Jaxpr``."""
    if isinstance(program, jcore.Jaxpr):
        return program
    inner = getattr(program, "jaxpr", None)
    if inner is not None:
        return as_jaxpr(inner)
    raise TypeError(f"not a jaxpr-like program: {type(program)!r}")


def _inner_jaxprs(eqn: jcore.JaxprEqn) -> Iterator[jcore.Jaxpr]:
    """Inner programs carried by one equation's params (scan/cond/pjit/
    pallas_call/…), each as a bare ``Jaxpr``."""
    for p in eqn.params.values():
        for sub in jax.tree.leaves(
                p, is_leaf=lambda x: isinstance(
                    x, (jcore.Jaxpr, jcore.ClosedJaxpr))):
            if isinstance(sub, jcore.ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, jcore.Jaxpr):
                yield sub


def iter_jaxprs(program) -> Iterator[jcore.Jaxpr]:
    """The program and every (transitively) inner jaxpr, outer-first."""
    stack = [as_jaxpr(program)]
    while stack:
        jx = stack.pop()
        yield jx
        for eqn in jx.eqns:
            stack.extend(_inner_jaxprs(eqn))


def iter_eqns(program) -> Iterator[jcore.JaxprEqn]:
    """Every equation of the program, recursing into inner jaxprs."""
    for jx in iter_jaxprs(program):
        yield from jx.eqns


def iter_avals(program) -> Iterator[Any]:
    """Every abstract value the program touches: in/out/const vars of each
    (inner) jaxpr plus each equation's operand and result avals — the
    stream ``NoOversizedBuffer``-style rules scan for forbidden shapes."""
    for jx in iter_jaxprs(program):
        for v in (*jx.invars, *jx.outvars, *jx.constvars):
            if hasattr(v, "aval"):
                yield v.aval
        for eqn in jx.eqns:
            for v in (*eqn.invars, *eqn.outvars):
                if hasattr(v, "aval"):
                    yield v.aval


def count_primitive(program, name: str) -> int:
    """Occurrences of primitive ``name`` anywhere in the program."""
    return sum(1 for eqn in iter_eqns(program) if eqn.primitive.name == name)


def primitive_names(program) -> List[str]:
    """Sorted distinct primitive names anywhere in the program."""
    return sorted({eqn.primitive.name for eqn in iter_eqns(program)})


def kernel_jaxprs(program) -> List[jcore.Jaxpr]:
    """The ``pallas_call`` kernel-body jaxprs of the program (possibly
    nested inside scans etc.) — the scope of kernel-local rules."""
    out: List[jcore.Jaxpr] = []
    for eqn in iter_eqns(program):
        if eqn.primitive.name == "pallas_call":
            for sub in _inner_jaxprs(eqn):
                out.append(sub)
    return out


def avals_with_dim(program, size: int) -> List[Any]:
    """Avals with ``size`` as one of their dimensions — e.g. every
    max_len-sized buffer a paged prefill was supposed to have deleted."""
    return [a for a in iter_avals(program)
            if hasattr(a, "shape") and size in tuple(a.shape)]


def donated_flat_indices(example_args: Tuple, donate_argnums) -> List[int]:
    """Map top-level ``donate_argnums`` of a jitted call to FLAT argument
    positions (one per pytree leaf) — the numbering StableHLO's
    ``%argN`` uses, which ``DonationEffective`` matches aliasing
    attributes against."""
    donate = set(donate_argnums)
    flat: List[int] = []
    pos = 0
    for i, arg in enumerate(example_args):
        n = len(jax.tree.leaves(arg))
        if i in donate:
            flat.extend(range(pos, pos + n))
        pos += n
    return flat


def stablehlo_arg_attrs(lowered) -> List[Optional[str]]:
    """Per-argument attribute blobs of the lowered module's public
    ``main`` — index k holds the ``{...}`` attribute text of ``%argk``
    (None when the argument carries no attributes).  This is where jax
    records effective buffer donation (``tf.aliasing_output``)."""
    import re
    txt = lowered.as_text() if hasattr(lowered, "as_text") else str(lowered)
    m = re.search(r"func\.func public @main\((.*?)\)\s*->", txt, re.S)
    if m is None:
        raise ValueError("could not find @main signature in lowered module")
    sig = m.group(1)
    attrs: List[Optional[str]] = []
    for am in re.finditer(r"%arg(\d+):\s*[^,{]*(\{[^}]*\})?", sig):
        idx = int(am.group(1))
        while len(attrs) <= idx:
            attrs.append(None)
        attrs[idx] = am.group(2)
    return attrs
