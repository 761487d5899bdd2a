"""Records tests/data/scoped_step.xplane.pb, the chip trace that
tests/test_profile.py reads: a two-layer Topology (`attn`, a `gqa_attention`
whose rows run one at a time in a `lax.map` loop around the Mosaic kernels
`flash_attn_fwd` / `flash_attn_bwd`; `out`, an `fc`) under a squared-error
cost, Adam, five steps of `SGD.train` under the profiler after the step has
compiled. The Python tracer is off and the `/host:metadata` plane (the
compiled modules' HLO protos, 0.5 MB that no reader here uses) is cut out of
the file, every other byte as the profiler wrote it, so that the file stays
small; the program's `paddle:` spans are TraceMes and stay.

    chiprun -- python3 tests/data/record_scoped_step.py

writes chiprun_out/scoped_step.xplane.pb (copy it to tests/data/).
"""

import glob
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402

import paddle_tpu as paddle  # noqa: E402

ROWS, T, D = 2, 512, 256


def without_plane(buf, name):
    """An XSpace's bytes without its planes called ``name``."""
    from paddle_tpu.observability import profile

    out, prev = bytearray(), 0
    for field, _, (start, end) in profile._fields(buf, 0, len(buf)):
        plane_name = next((profile._text(buf, v) for f, _, v in
                           profile._fields(buf, start, end) if f == 2), "") \
            if field == 1 else None
        if plane_name != name:
            out += buf[prev:end]
        prev = end
    return bytes(out)


def build():
    x = paddle.layer.data(
        name="x", type=paddle.data_type.dense_vector_sequence(D))
    y = paddle.layer.data(
        name="y", type=paddle.data_type.dense_vector_sequence(D))
    attn = paddle.layer.gqa_attention(
        input=x, num_heads=2, num_kv_heads=1, head_dim=128,
        mask=("causal", T), name="attn")
    out = paddle.layer.fc(input=attn, size=D, act=paddle.activation.Linear(),
                          name="out")
    return paddle.layer.square_error_cost(input=out, label=y, name="cost")


def batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [[(rng.randn(T, D).astype("float32"),
              rng.randn(T, D).astype("float32")) for _ in range(ROWS)]
            for _ in range(n)]


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_scoped_step: needs a TPU")
    out = os.path.join(ROOT, "chiprun_out")
    tmp = os.path.join(out, "scoped_step_dir")
    os.makedirs(out, exist_ok=True)
    shutil.rmtree(tmp, ignore_errors=True)
    cost = build()
    trainer = paddle.SGD(cost=cost, parameters=paddle.parameters.create(cost),
                         update_equation=paddle.optimizer.Adam(
                             learning_rate=1e-3),
                         mixed_precision=True)
    feeding = {"x": 0, "y": 1}
    trainer.train(lambda: iter(batches(2)), num_passes=1, feeding=feeding)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    trainer.train(lambda: iter(batches(5, seed=1)), num_passes=1,
                  feeding=feeding)
    jax.profiler.stop_trace()
    src, = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    dst = os.path.join(out, "scoped_step.xplane.pb")
    with open(src, "rb") as f, open(dst, "wb") as g:
        g.write(without_plane(f.read(), "/host:metadata"))
    shutil.rmtree(tmp)
    print("scoped_step bytes", os.path.getsize(dst))


if __name__ == "__main__":
    main()
