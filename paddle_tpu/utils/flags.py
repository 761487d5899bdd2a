"""Process-level flag registry.

Analog of the ~30 gflags in reference paddle/utils/Flags.cpp (use_gpu,
trainer_count, port, trainer_id, beam_size, log_period, ...). On TPU most
device/network flags become mesh/runtime knobs; unknown flags are accepted
and warned about rather than fatal, because reference configs pass
--config_args freely.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict


class _Flags:
    def __init__(self):
        self._defs: Dict[str, Any] = {}
        self._values: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def define(self, name: str, default: Any, help_str: str = ""):
        with self._lock:
            self._defs[name] = (default, help_str)
            self._values.setdefault(name, default)

    def __getattr__(self, name: str):
        values = object.__getattribute__(self, "_values")
        if name in values:
            return values[name]
        raise AttributeError(f"unknown flag {name!r}")

    def get(self, name: str, default: Any = None):
        return self._values.get(name, default)

    def set(self, name: str, value: Any):
        with self._lock:
            self._values[name] = value

    def set_if_known(self, name: str, value: Any):
        """Set a flag; unknown names are stored anyway (gflags configs pass
        through freely) but flagged for the caller."""
        with self._lock:
            known = name in self._defs
            self._values[name] = value
        return known

    def to_dict(self):
        return dict(self._values)


FLAGS = _Flags()


def define_flag(name, default, help_str=""):
    FLAGS.define(name, default, help_str)


# Reference flag set (paddle/utils/Flags.cpp + trainer-local flags, SURVEY A.6),
# re-interpreted for TPU where meaningful.
define_flag("use_gpu", False, "kept for config parity; all compute is XLA/TPU")
define_flag("use_tpu", True, "route compute through the TPU backend")
define_flag("trainer_count", 1, "data-parallel shards (mesh 'data' axis size)")
define_flag("trainer_id", int(os.environ.get("PADDLE_TRAINER_ID", 0)), "process index")
define_flag("num_gradient_servers", 1, "kept for parity; collectives replace pservers")
define_flag("port", 7164, "coordination service port (jax.distributed)")
define_flag("ports_num", 1, "parity only")
define_flag("ports_num_for_sparse", 0, "parity only")
define_flag("nics", "", "parity only")
define_flag("rdma_tcp", "tcp", "parity only; ICI/DCN replace RDMA/TCP")
define_flag("comment", "", "job comment")
define_flag("log_period", 100, "batches between log lines")
define_flag("log_period_server", 500, "parity only")
define_flag("dot_period", 1, "batches between progress dots")
define_flag("beam_size", 1, "default beam width for generation")
define_flag("show_layer_stat", False, "print per-layer value stats each batch")
define_flag("show_parameter_stats_period", 0, "batches between parameter stat dumps")
define_flag("pack_sequences", False,
            "pack several ragged samples per feed row with segment ids "
            "(docs/packing.md)")
define_flag("pack_max_len", 0,
            "packed row capacity T (0 = auto: 2x the batch's longest "
            "sample, bucketed)")
define_flag("bucket_rounding", 0,
            "pad sequence T to a multiple of N instead of the next power "
            "of two (0 = power-of-two)")
define_flag("checkgrad_eps", 1e-5, "finite-difference step for grad checks")
define_flag("load_missing_parameter_strategy", "fail", "fail|rand|zero")
define_flag("init_model_path", "", "checkpoint dir to warm-start from")
define_flag("start_pass", 0, "resume pass number")
define_flag("num_passes", 1, "training passes")
define_flag("save_dir", "", "checkpoint output dir")
define_flag("saving_period", 1, "passes between checkpoints")
define_flag("test_period", 0, "batches between test runs (0 = per pass)")
define_flag("prev_batch_state", False, "carry RNN state across batches")
define_flag("parallel_nn", False, "per-layer device placement (maps to shardings)")
define_flag("seed", 1, "global RNG seed (deterministic by default, like gserver)")
define_flag("pipeline_depth", 2,
            "train-loop software pipeline depth: up to depth-1 dispatched "
            "steps stay in flight while the host feeds the next batch; "
            "0/1 = strictly synchronous (docs/pipeline.md)")
define_flag("host_table_min_rows", 0,
            "sparse_update tables with at least this many rows train "
            "host-resident: host-RAM store + per-batch device row cache "
            "(0 = only ParamAttr(host_resident=True) tables; "
            "docs/embedding_cache.md)")
define_flag("host_cache_rows", 0,
            "device row-cache capacity per host-resident table (rows; "
            "0 = auto: power-of-two bucket of the batch's unique-id "
            "count, grown on demand)")
define_flag("debug_nans", False, "enable jax debug_nans (FP-trap analog, TrainerMain.cpp:49)")
