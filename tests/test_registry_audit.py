"""Every SURVEY A.1 layer type name resolves in the registry.

The reference registers 95 layer types via REGISTER_LAYER macros
(paddle/gserver/layers/Layer.h:31-37) plus 4 cost/validation types wired
by name in the DSL cost table
(python/paddle/trainer/config_parser.py:2639-2651,
paddle/gserver/layers/Layer.cpp:102). A reference config naming any of
them must parse here. VERDICT r4 closed the last two
(auc-validation / pnpair-validation); this pins 99/99.

Two more audits of the tree against what it says of itself: the README's
tooling block names files that exist, and no module of the package picks
a code path from a PADDLE_TPU_* environment variable.
"""

import os
import re

import paddle_tpu  # noqa: F401  - populates the registry
from paddle_tpu.core.layer import LAYER_REGISTRY

A1_MACRO_NAMES = """
addto agent average batch_norm bilinear_interp blockexpand clip concat
concat2 conv3d conv_shift convex_comb cos cos_vm crf crf_decoding
crf_error crop cross_entropy_over_beam ctc cudnn_batch_norm cudnn_conv
cudnn_convt data data_norm deconv3d detection_output eos_id exconv
exconvt expand fc featmap_expand gated_recurrent gather_agent get_output
gru_step hsigmoid huber_classification huber_regression interpolation
kmax_seq_score lambda_cost lstm_step lstmemory max maxid maxout
mdlstmemory mixed mkldnn_conv mkldnn_fc mkldnn_pool
multi_binary_label_cross_entropy multi_class_cross_entropy_with_selfnorm
multibox_loss multiplex nce norm out_prod pad pool pool3d power prelu
print priorbox recurrent recurrent_layer_group resize rotate row_conv
row_l2_norm sampling_id scale_shift scaling scatter_agent selective_fc
seq_slice seqconcat seqlastins seqreshape slope_intercept smooth_l1
soft_binary_class_cross_entropy spp square_error sub_nested_seq subseq
sum_cost sum_to_one_norm switch_order tensor trans warp_ctc
""".split()

NAME_WIRED_COST_TYPES = ["multi-class-cross-entropy", "rank-cost",
                         "auc-validation", "pnpair-validation"]


def test_a1_layer_types_all_registered():
    assert len(A1_MACRO_NAMES) == 95
    wanted = A1_MACRO_NAMES + NAME_WIRED_COST_TYPES
    missing = [n for n in wanted if n not in LAYER_REGISTRY]
    assert not missing, f"A.1 names absent from the registry: {missing}"



_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_readme_commands_name_files_in_the_tree():
    """Every `python <file>` / `bash <file>` in README.md's code blocks is a
    file of the checkout: the README is held to the tree, not to memory."""
    with open(os.path.join(_ROOT, "README.md")) as f:
        blocks = re.findall(r"```(?:bash)?\n(.*?)```", f.read(), re.S)
    named = set()
    for block in blocks:
        named |= set(re.findall(
            r"(?:^|\s)(?:python3?|bash)\s+([\w./-]+\.(?:py|sh))\b", block))
    assert "benchmark/run.py" in named and len(named) >= 8, sorted(named)
    missing = sorted(n for n in named
                     if not os.path.exists(os.path.join(_ROOT, n)))
    assert not missing, missing


#: the PADDLE_TPU_* names the package may read, each a deployment setting
#: (the simplicity guide: addresses, paths and credentials stay configurable)
_ENV_SETTINGS = {
    "PADDLE_TPU_DATA_HOME": "a path: where datasets are cached",
    "PADDLE_TPU_FAULT_PLAN": "a path: the chaos suites' scripted fault plan",
}


def test_no_module_picks_a_code_path_from_a_paddle_tpu_env_var():
    """Choices between implementations come from what the code observes
    (backend, shapes, arguments a caller passes), never from a PADDLE_TPU_*
    variable in the environment, which no test matrix or benchmark cell
    would cover. A new name has to be entered above with what it is."""
    found = {}
    for d, _dirs, files in os.walk(os.path.join(_ROOT, "paddle_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(d, fn)
                with open(path) as f:
                    for name in re.findall(
                            r"""["'](PADDLE_TPU_[A-Z0-9_]*)["']""", f.read()):
                        found[name] = os.path.relpath(path, _ROOT)
    assert set(found) <= set(_ENV_SETTINGS), found
