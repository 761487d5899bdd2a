"""A Pallas kernel's share (%) of its roofline over the traced steps: the
least seconds the chip could take for every call of the kernel that the
trace holds (benchmark/kernels/<kernel>.py at the step's shapes, the larger
of FLOPs over the bf16 peak and bytes over the HBM peak), over the seconds
those calls took on the device.

The calls are found by name: the program gives each `pl.pallas_call` a
`name=`, JAX's transforms wrap it (`jvp_<name>_.N`, `transpose_jvp_<name>__.N`,
under a shard_map more) and the trace shows the instruction's name, so a
call is an `XLA Ops` event of the first device whose name contains the
kernel's own. `calls` maps each such name to the count function of
kernels/<kernel>.py that prices it. Nothing to read (no such event, as in
a program whose kernels carry no name, or no peaks) gives None.
"""

from benchmark import correct


def read(ctx, kernel, calls, feed, width, itemsize):
    raw, peak = ctx.get("raw"), ctx.get("peak")
    if peak is None or not raw or not raw["devices"]:
        return None
    count = correct.load_module(f"kernels/{kernel}.py")
    B, T = ctx["shape"][feed][:2]
    H = ctx["config"][width]
    events = raw["devices"][min(raw["devices"])]
    least = seen = 0.0
    for own_name, direction in calls.items():
        sec, _ = count.least_seconds(
            *getattr(count, direction)(B, T, H, itemsize), peak)
        for name, _, dur_ns in events:
            if own_name in name:
                least += sec
                seen += dur_ns / 1e9
    return 100.0 * least / seen if seen else None
