"""Operations of the Perona scoring forward pass, from shapes and widths.

Counted from the model's equations (paper §III-C), not from the
compiled program, so that a change to the implementation cannot change
the count. Only multiply-adds that feed the scoring outputs (anomaly
probability, type logits, codes) are counted, two operations each; the
decoder is not on the scoring path and elementwise work is left out.
"""

from __future__ import annotations


def perona_row_flops(feature_dim: int, edge_dim: int, *, code_dim: int,
                     hidden: int, heads: int, tag_hops: int, n_types: int,
                     predecessors: int) -> int:
    """Operations to score one graph row with ``predecessors`` in-edges."""
    f, a, k, h, t, p = (feature_dim, edge_dim, code_dim, hidden, n_types,
                        predecessors)
    del heads  # heads split K; the per-row work does not depend on them
    mac = 0
    mac += f * h + h * k                 # encoder MLP
    mac += k * k                         # query
    mac += 2 * p * k * k                 # key and value of each neighbour
    mac += 2 * p * a * k                 # edge terms of key and value
    mac += 2 * p * k                     # attention scores, weighted sum
    mac += (tag_hops + 1) * k * k        # TAGConv hop weights
    mac += tag_hops * p * k              # hop aggregation (adds)
    mac += 2 * k * k                     # output and root transforms
    mac += k * h + h                     # anomaly head
    mac += k * t                         # benchmark-type probe
    return 2 * mac


def perona_flops(rows: int, feature_dim: int, edge_dim: int, model: dict
                 ) -> int:
    """Operations to score ``rows`` real (unpadded) rows."""
    return rows * perona_row_flops(
        feature_dim, edge_dim,
        **{k: model[k] for k in ("code_dim", "hidden", "heads", "tag_hops",
                                 "n_types", "predecessors")})
