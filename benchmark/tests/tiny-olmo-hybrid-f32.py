"""Plain reference of the rehearsal's tiny dense hybrid decoder: the same
code as the real configuration's (`benchmark/reference/dense_hybrid_causal.py`)."""

from benchmark.reference import dense_hybrid_causal

follow = dense_hybrid_causal.follow
