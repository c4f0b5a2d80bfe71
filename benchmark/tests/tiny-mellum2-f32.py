"""Plain reference of the rehearsal's tiny window-and-full decoder: the same
code as the real configuration's (`benchmark/reference/window_causal.py`)."""

from benchmark.reference import window_causal

follow = window_causal.follow
