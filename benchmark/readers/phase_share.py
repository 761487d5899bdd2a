"""Share (%) of the window's seconds that the loop's thread spent in the given
phases, from the program's histogram paddle_train_step_seconds."""


def read(ctx, phases):
    if not all(p in ctx["phases"] for p in phases):
        return None
    return 100.0 * sum(ctx["phases"][p][0] for p in phases) / ctx["window_s"]
