"""Closed-loop batches: ``pool_calls`` distinct calls of ``docs_per_call``
documents of the mix's ``text`` (``log`` or ``protein``).

Every call holds the same multiset of document lengths (quantiles of the
mix's length distribution), shuffled by the seed: the work of a call does
not change with the seed, only its order and bytes.
"""

from __future__ import annotations

from chipbench import gen


def make(mix: dict, seed: int, seconds: float) -> list[list[bytes]]:
    lengths = gen.length_quantiles(mix, int(mix["docs_per_call"]))
    pools = (gen.line_pools(gen.rng_for(seed, 0)) if mix["text"] == "log"
             else None)
    batches = []
    for c in range(int(mix["pool_calls"])):
        rng = gen.rng_for(seed, 1, c)
        lens = rng.permutation(lengths)
        if mix["text"] == "log":
            rate = float(mix["special_rate"])
            batches.append([gen.log_doc(rng, pools, int(n), rate)
                            for n in lens.tolist()])
        elif mix["text"] == "protein":
            batches.append(gen.protein_seqs(rng, lens,
                                            mix["residue_percent"]))
        else:
            raise ValueError(f"unknown text kind {mix['text']!r}")
    return batches
