"""Plain reference of the rehearsal's tiny hybrid decoder: the same code as
the real configuration's (`benchmark/reference/hybrid_causal.py`)."""

from benchmark.reference import hybrid_causal

follow = hybrid_causal.follow
