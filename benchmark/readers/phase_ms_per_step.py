"""Mean host milliseconds a step spends in one phase of the public loop, from
the program's histogram paddle_train_step_seconds{phase=...}: the phase's
seconds over the window, divided by its count."""


def read(ctx, phase):
    total, count = ctx["phases"].get(phase, (0.0, 0))
    return 1e3 * total / count if count else None
