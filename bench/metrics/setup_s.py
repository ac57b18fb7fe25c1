"""setup_s: process start to the end of warm-up, host clock.

Imports, JAX's start, the seeded data and its copy to the chips, and one
query through the cell's program, which compiles it or loads it from the
persistent compilation cache.
"""


def read(run):
    return run.setup_s
