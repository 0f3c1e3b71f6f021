"""setup_s (s): process start to the window's first timed submit (host
clock): pool boot, the node's jax import and backend start, the two warm
jobs at the cell's band shape."""


def read(run):
    return run.rec.setup_s
