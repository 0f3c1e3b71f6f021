"""mpoints_per_s (Mpoints/s): points of every band collected inside the
window, those of the job still running at its close too, over the
window's seconds (host clock)."""


def read(run):
    rec = run.rec
    return rec.window_bands() * rec.points_per_band / rec.window_s / 1e6
