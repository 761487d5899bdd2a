"""The delta rule's state-pass kernels' share (%) of their roofline over the
traced steps: the least seconds the chip could take for the state passes the
traced steps need (benchmark/kernels/gdn.py), over the seconds that every
event of the named kernels took on the device.

The need is reckoned from the model and not from the program's calls: each
traced step passes every DeltaNet layer once forward and once backward over
B x (value heads) rows of T tokens, so steps x layers x (forward + backward)
at the step's B and T, the configuration's head counts and widths, and the
chunk its file states under assumed.chunk_tokens. How the program splits
that into calls (a row at a time, the whole batch at once) does not enter,
and a forward pass computed again for the backward pass is time with no need
beside it, as in train_step_mfu. The events are found by name, as
readers/kernel_roofline.py finds them. Nothing to read (no such event, as in
a program that takes the scan, or no peaks) gives None.
"""

from benchmark import correct


def read(ctx, calls, feed, itemsize):
    raw, peak = ctx.get("raw"), ctx.get("peak")
    if peak is None or not raw or not raw["devices"]:
        return None
    events = raw["devices"][min(raw["devices"])]
    seen = sum(dur_ns / 1e9 for name, _, dur_ns in events
               if any(own_name in name for own_name in calls))
    if not seen:
        return None
    count = correct.load_module("kernels/gdn.py")
    config = ctx["config"]
    a, chunk = config["model"]["args"], config["assumed"]["chunk_tokens"]
    B, T = ctx["shape"][feed][:2]
    layers = sum((n + 1) % a["full_attention_interval"] != 0
                 for n in range(a["num_hidden_layers"]))
    shape = (B * a["linear_num_value_heads"], -(-T // chunk), chunk,
             a["linear_key_head_dim"], a["linear_value_head_dim"], itemsize)
    least = sum(count.least_seconds(*getattr(count, direction)(*shape), peak)[0]
                for direction in set(calls.values()))
    return 100.0 * ctx["cell"]["trace_steps"] * layers * least / seen
