"""Plain reference of the CPU rehearsal's tiny decoder: the same module as
the real configuration's, at the sizes of `tiny-sdar-f32.json`."""

from benchmark.reference import blockdiff

follow = blockdiff.follow
