"""Plain reference of the rehearsal's tiny latent-attention decoder: the
same code as the real configuration's
(`benchmark/reference/latent_hc_causal.py`)."""

from benchmark.reference import latent_hc_causal

follow = latent_hc_causal.follow
