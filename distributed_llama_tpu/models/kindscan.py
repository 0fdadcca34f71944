"""A per-layer list of kinds run as scans: what the two slot-and-pages
forwards (``models/sambay.py``: one stack of weights a layer kind;
``models/laguna.py``: a mixer's stack and an FFN's stack a layer) share of
walking such a list. A layer's SIGNATURE is the tuple of the stacks its
weights lie in; a repeating unit of the list of signatures is one
``lax.scan`` over its repeats, each stack indexed by how many of its layers
came before.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.linear import StackedQ40


def is_packed(v) -> bool:
    from ..io.loader import Q40Kernel, Q40KernelNb, Q40KernelNbI4

    return isinstance(v, (Q40Kernel, Q40KernelNb, Q40KernelNbI4))


def segments(sigs, longest: int = 8) -> list:
    """[(first layer, unit, repeats)]: the list cut into repeating units
    (the shortest unit of two to ``longest`` layers of more than one
    signature that repeats at once, else one layer and its run)."""
    sigs = list(sigs)
    out, i = [], 0
    while i < len(sigs):
        unit, reps = tuple(sigs[i:i + 1]), 1
        for u in range(2, min(longest, (len(sigs) - i) // 2) + 1):
            cand = tuple(sigs[i:i + u])
            n = 1
            while tuple(sigs[i + n * u:i + (n + 1) * u]) == cand:
                n += 1
            if n > 1 and len(set(cand)) > 1:
                unit, reps = cand, n
                break
        if len(unit) == 1:
            while tuple(sigs[i + reps:i + reps + 1]) == unit:
                reps += 1
        out.append((i, unit, reps))
        i += len(unit) * reps
    return out


def run_layers(sigs, stack_of, carry, layer_fn, upto: int | None = None):
    """Layers 0 .. ``upto`` - 1 (default all) of the list ``sigs`` through
    ``layer_fn(sig, lw, carry, layer, idx)``, a repeating unit a scan.
    ``stack_of(name)`` is a stack's leaves, a leading layer axis each;
    ``lw`` holds the leaves of the layer's stacks, packed Q40 stacks as
    ``StackedQ40`` views (the kernels index them: ops/linear) and the rest
    sliced; ``layer`` is its place in the list and ``idx[name]`` its place
    in stack ``name``."""
    seen: dict = {}
    for first, unit, reps in segments(list(sigs)[:upto]):
        names = sorted({s for sig in unit for s in sig})
        per = {s: sum(s in sig for sig in unit) for s in names}
        occ = [{s: sum(s in sig for sig in unit[:u]) for s in sig}
               for u, sig in enumerate(unit)]
        base = {s: seen.get(s, 0) for s in names}
        packed, sliced = {}, {}
        for s in names:
            stack = stack_of(s)

            def cut(a, s=s):
                # one layer of the stack a repeat is scanned as it lies
                # (the chip's compiler copies leaves around a reshape and
                # an index that say nothing: 16 MiB more of temporaries in
                # a hybrid spec's step)
                a = a[base[s]:base[s] + reps * per[s]]
                return a if per[s] == 1 else a.reshape(reps, per[s],
                                                       *a.shape[1:])

            packed[s] = {k: v for k, v in stack.items() if is_packed(v)}
            sliced[s] = {k: jax.tree_util.tree_map(cut, v)
                         for k, v in stack.items() if k not in packed[s]}

        def body(carry, xs, first=first, unit=unit, per=per, occ=occ,
                 base=base, packed=packed):
            j, sl = xs
            for u, sig in enumerate(unit):
                lw, idx = {}, {}
                for s in sig:
                    idx[s] = base[s] + j * per[s] + occ[u][s]
                    lw.update(jax.tree_util.tree_map(
                        lambda a, o=occ[u][s], n=per[s]: a if n == 1
                        else a[o], sl[s]))
                    lw.update({k: StackedQ40(v, idx[s])
                               for k, v in packed[s].items()})
                carry = layer_fn(sig, lw, carry, first + j * len(unit) + u,
                                 idx)
            return carry, None

        carry, _ = jax.lax.scan(
            body, carry, (jnp.arange(reps, dtype=jnp.int32), sliced))
        for s in names:
            seen[s] = base[s] + reps * per[s]
    return carry


def merge_lead(a, n_lead: int):
    """``a`` with its first ``n_lead`` axes merged into one."""
    return a.reshape(-1, *a.shape[n_lead:])


def insert_sequence(cache, one, row, table: jax.Array, page_size: int):
    """Put a sequence's cache (the forward's ``init_cache(spec)``,
    prefilled) into row ``row`` of the paged cache (a NamedTuple of the
    same fields): what it keeps a slot of (state, rings: (layers, rows,
    ...)) whole, its ``k`` / ``v`` (layers, KV heads, seq_len, head) page by
    page into the pools (layers, pages, KV heads, page_size, head) through
    ``table`` (max_pages,) (entries past the sequence's pages point at the
    scrap page)."""
    def rows(whole, part):
        return jax.lax.dynamic_update_slice(
            whole, part[:, None].astype(whole.dtype),
            (0, row) + (0,) * (part.ndim - 1))

    def pages(pool, seq):
        f, n_kv, _, hs = seq.shape
        paged = seq.reshape(f, n_kv, table.shape[0], page_size, hs)
        return pool.at[:, table].set(
            jnp.swapaxes(paged, 1, 2).astype(pool.dtype))

    return type(cache)(*(
        (pages if name in ("k", "v") else rows)(whole, part)
        for name, whole, part in zip(cache._fields, cache, one)))
