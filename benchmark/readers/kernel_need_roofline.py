"""A kernel family's share (%) of its roofline over the traced steps, with the
need reckoned from the model and not from the program's calls: the least
seconds the chip could take for what the traced steps need of the kernels
(benchmark/kernels/<counts>.py), over the seconds that every event of the
named kernels took on the device.

The metric's file gives the recipe: `counts` names the module of
benchmark/kernels/, `shape` lists that module's arguments in order, each a
dimension of the step's feed `feed` ("B", "L"), "itemsize", a number, or the
name of one of the configuration's model arguments, and `layers` names the
model argument that counts the layers a step passes once forward and once
backward. So the need is steps x layers x (forward + backward) at the step's
shape. How the program splits that into launches (one a layer, two, a row at
a time) does not enter, nor does a tile it rounds up to, and a forward pass
computed again for the backward pass is time with no need beside it, as in
train_step_mfu: the share cannot pass 100%. The events are found by name, as
readers/kernel_roofline.py finds them. Nothing to read (no such event, as in
a program without these kernels, or no peaks) gives None.

readers/gdn_kernel_roofline.py is this reader with its recipe written in
code (its layer count and rows are derived, not named); a `benchmark` PR
that gives kernels/gdn.py the raw arguments can point its metric here.
"""

from benchmark import correct


def read(ctx, calls, counts, feed, shape, layers, itemsize):
    raw, peak = ctx.get("raw"), ctx.get("peak")
    if peak is None or not raw or not raw["devices"]:
        return None
    events = raw["devices"][min(raw["devices"])]
    seen = sum(dur_ns / 1e9 for name, _, dur_ns in events
               if any(own_name in name for own_name in calls))
    if not seen:
        return None
    count = correct.load_module(f"kernels/{counts}.py")
    a = ctx["config"]["model"]["args"]
    B, L = ctx["shape"][feed][:2]
    given = {"B": B, "L": L, "itemsize": itemsize}
    dims = tuple(s if not isinstance(s, str) else given[s] if s in given
                 else a[s] for s in shape)
    least = sum(count.least_seconds(*getattr(count, direction)(*dims), peak)[0]
                for direction in set(calls.values()))
    return 100.0 * ctx["cell"]["trace_steps"] * a[layers] * least / seen
