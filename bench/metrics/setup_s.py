"""setup_s (s): process start to the window's first timed submit (host
clock): pool boot, the nodes' jax import and backend start, one band in
every node (it compiles), the two warm jobs at the cell's band shape."""


def read(run):
    return run.rec.setup_s
