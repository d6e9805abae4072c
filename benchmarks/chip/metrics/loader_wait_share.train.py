"""Share of the train window spent inside the loader's ``next()``, timed
by the benchmark's own spans around the feed handed to ``TrainLoop``."""


def read(ctx):
    w = ctx.window
    if "loader_wait_s" not in w or not w.get("steps"):
        return None
    return 100.0 * w["loader_wait_s"] / w["window_s"]
