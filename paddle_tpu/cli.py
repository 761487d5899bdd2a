"""``paddle`` command-line dispatcher.

Analog of paddle/scripts/submit_local.sh.in:96-122 (``paddle
train|pserver|merge_model|version`` dispatch) + paddle/trainer/
TrainerMain.cpp:32-65 (the train entry: parse config, build trainer,
run). The ``master`` subcommand serves the fault-tolerant task-queue
service (go/master parity; native/master.cc here).
"""

from __future__ import annotations

import argparse
import os
import sys


def cmd_version(args):
    import jax

    from paddle_tpu.version import __version__

    print(f"PaddleTPU version {__version__}")
    print(f"  jax {jax.__version__}; devices: "
          f"{[d.platform for d in jax.devices()]}")
    return 0


def cmd_train(args):
    """paddle train --config=conf.py [--job=train|test|checkgrad]
    [--config_args k=v,...] [--num_passes N] [--save_dir DIR]
    [--init_model_path tar] [--use_bf16] [--batch_size B]
    (TrainerMain.cpp flow; --job parity with Trainer.cpp:332-334:
    test evaluates a saved model, checkgrad finite-differences the
    whole net)."""
    from paddle_tpu.utils.flags import FLAGS

    for fname in ("log_period", "test_period",
                  "show_parameter_stats_period", "saving_period",
                  "pipeline_depth",
                  "pack_sequences", "pack_max_len", "bucket_rounding",
                  "host_table_min_rows", "host_cache_rows"):
        v = getattr(args, fname, None)
        if v is not None:
            FLAGS.set(fname, v)

    # observability egress (opt-in): --metrics_port serves /metrics,
    # /healthz, /trace; --trace_dir collects Chrome trace spans (written
    # at exit); --metrics_interval appends periodic JSON snapshots for
    # headless runs. All host-side — the compiled programs are untouched.
    from paddle_tpu.observability import exporter as obs_exporter

    obs_handles = obs_exporter.configure(
        metrics_port=getattr(args, "metrics_port", None),
        trace_dir=getattr(args, "trace_dir", None),
        metrics_interval=getattr(args, "metrics_interval", 0.0) or 0.0)
    try:
        return _cmd_train_impl(args)
    finally:
        obs_exporter.shutdown(obs_handles)


def _cmd_train_impl(args):
    import jax

    from paddle_tpu import reader as reader_mod
    from paddle_tpu.core.parameters import Parameters
    from paddle_tpu.io import checkpoint
    from paddle_tpu.trainer.config_parser import parse_config
    from paddle_tpu.trainer.trainer import SGD
    from paddle_tpu.utils import logger
    from paddle_tpu.utils.flags import FLAGS

    cfg = parse_config(args.config, args.config_args or "")
    topo = cfg.topology()
    logger.info("config %s: %d layers, %d params", args.config,
                len(topo.layers), len(topo.param_specs()))
    params = Parameters.from_topology(topo)
    if args.init_model_path:
        # from_tar is a constructor: copy the loaded values into THIS
        # parameter set (missing names keep their fresh init)
        with open(args.init_model_path, "rb") as f:
            loaded = Parameters.from_tar(f)
        copied = [n for n in loaded.names() if n in params]
        for name in copied:
            params.set(name, loaded.get(name))
        if not copied:
            print(f"init_model_path {args.init_model_path}: no parameter "
                  "names match this config — refusing to train from "
                  "scratch silently", file=sys.stderr)
            return 1
        logger.info("warm start: %d/%d parameters loaded from %s",
                    len(copied), len(list(params.names())),
                    args.init_model_path)
    job = getattr(args, "job", "train")
    if job == "test" and not args.init_model_path:
        print("--job=test requires --init_model_path (a saved model to "
              "evaluate)", file=sys.stderr)
        return 1
    # multiple COST outputs train against their SUM (the reference trainer
    # accumulates every output-layer cost, e.g. the 24-task
    # traffic_prediction config); non-cost outputs stay extra layers
    from paddle_tpu.layers.cost import is_cost_type

    cost = cfg.outputs[0]
    summed = len(cfg.outputs) > 1 and all(
        is_cost_type(o.type) for o in cfg.outputs)
    if summed:
        from paddle_tpu import layer as _layer
        cost = _layer.addto(input=list(cfg.outputs), bias_attr=False)
    trainer = SGD(cost=cost, parameters=params,
                  update_equation=cfg.optimizer,
                  extra_layers=cfg.outputs if summed
                  else (cfg.outputs[1:] or None),
                  evaluators=cfg.evaluators,
                  mixed_precision=bool(args.use_bf16))

    batch_size = args.batch_size or cfg.batch_size
    if cfg.data_sources is None and not cfg.data_direct:
        print("config defines no train data source "
              "(no define_py_data_sources2 / TrainData call)",
              file=sys.stderr)
        return 1
    train_reader = cfg.reader(for_test=False)
    if train_reader is None:
        print("config defines no train data source", file=sys.stderr)
        return 1
    test_reader = cfg.reader(for_test=True)
    feeding = cfg.feeding()

    def _train_flags_feeder():
        # honor the packing/bucketing flags so the diagnostic jobs
        # exercise the same feed shapes the real training run compiles
        from paddle_tpu.trainer.feeder import DataFeeder, \
            resolve_pack_flags
        pack, pml, br = resolve_pack_flags()
        return DataFeeder(trainer.topology.data_type(), feeding,
                          pack_sequences=pack, pack_max_len=pml,
                          bucket_rounding=br)

    if job == "test":
        # Tester flow (Trainer::test): evaluate over the test source (or
        # the train source if the config defines none) without updating.
        reader = test_reader or train_reader
        tr = trainer.test(reader=reader_mod.batch(reader, batch_size),
                          feeding=feeding)
        metrics = " ".join(f"{k}={v:.5f}" for k, v in tr.metrics.items())
        print(f"Test cost={tr.cost:.6f} {metrics}".rstrip())
        return 0

    if job == "time":
        # TrainerMain.cpp:58 parity (--job=time): replay one batch through
        # the jitted forward and forward-backward programs for log_period
        # iterations each and report ms/batch — so the reference's
        # benchmark scripts drive this CLI unchanged.
        import time as _time

        import jax.numpy as jnp

        feeder = _train_flags_feeder()
        batch = []
        for batch in reader_mod.batch(train_reader, batch_size)():
            break
        if not batch:
            print("--job=time: train reader yielded no data", file=sys.stderr)
            return 1
        feeds = feeder(batch)
        n = FLAGS.get("log_period", 100) or 100
        jparams = {k: jnp.asarray(v) for k, v in params.as_dict().items()}
        opt_state = trainer.optimizer.init(jparams)
        test_fn = trainer._build_test_step()
        train_fn = trainer._build_train_step()
        rng = jax.random.PRNGKey(FLAGS.get("seed", 1))

        def timed(run, sync):
            sync(run())                        # compile + warmup excluded
            t0 = _time.perf_counter()
            for _ in range(n):
                out = run()
            sync(out)                          # drain the dispatch queue
            return (_time.perf_counter() - t0) / n * 1e3

        fwd_ms = timed(lambda: test_fn(jparams, feeds),
                       lambda out: float(out[0]))

        def fwdbwd():
            nonlocal jparams, opt_state
            jparams, opt_state, cost, _ = train_fn(
                jparams, opt_state, rng, feeds)
            return cost

        fwdbwd_ms = timed(fwdbwd, float)
        print(f"job=time: batch_size={len(batch)} iters={n} "
              f"forward={fwd_ms:.3f} ms/batch "
              f"forward-backward={fwdbwd_ms:.3f} ms/batch")
        return 0

    if job == "checkgrad":
        from paddle_tpu.trainer.checkgrad import check_gradient

        feeder = _train_flags_feeder()
        batch = []
        for batch in reader_mod.batch(train_reader, batch_size)():
            break
        if not batch:
            print("checkgrad: train reader yielded no data", file=sys.stderr)
            return 1
        feeds = feeder(batch)
        jparams = {k: jax.numpy.asarray(v)
                   for k, v in params.as_dict().items()}
        ok, report = check_gradient(trainer.topology, trainer.cost_name,
                                    jparams, feeds,
                                    eps=args.checkgrad_eps)
        for name, r in sorted(report.items()):
            status = "ok" if r["ok"] else "FAIL"
            print(f"{status:4s} {name}: analytic={r['analytic']:+.6e} "
                  f"numeric={r['numeric']:+.6e} rel={r['rel_diff']:.3e}")
        print(f"checkgrad {'PASSED' if ok else 'FAILED'} "
              f"({len(report)} parameters)")
        return 0 if ok else 1

    save_dir = args.save_dir
    # elected save: with a master, exactly one trainer per election
    # window snapshots the model (go/master/service.go:474-503
    # RequestSaveModel; doc/design/cluster_train/save_model.md) — without
    # it every multi-process trainer would race on save_dir
    save_client = None
    trainer_id = getattr(args, "trainer_id", None) or f"trainer-{os.getpid()}"
    master_addr = getattr(args, "master_addr", None)
    if master_addr:
        from paddle_tpu.distributed.master_client import MasterClient

        try:
            host, port_str = master_addr.rsplit(":", 1)
            port_num = int(port_str)
        except ValueError:
            print(f"--master_addr {master_addr!r}: expected host:port",
                  file=sys.stderr)
            return 1
        save_client = MasterClient(host or "127.0.0.1", port_num)
    start_pass = getattr(args, "start_pass", 0) or 0
    if start_pass >= args.num_passes:
        print(f"--start_pass {start_pass} >= --num_passes "
              f"{args.num_passes}: nothing to train (num_passes is the "
              "total pass count)", file=sys.stderr)
        return 1
    save_every = getattr(args, "save_every_n_batches", 0) or 0
    if save_every and not save_dir:
        print("--save_every_n_batches requires --save_dir (where step "
              "snapshots live)", file=sys.stderr)
        return 1
    publish_every = getattr(args, "publish_every_n_batches", 0) or 0
    publish_dir = getattr(args, "publish_dir", None)
    if publish_every and not publish_dir:
        print("--publish_every_n_batches requires --publish_dir (where "
              "versioned serving bundles land)", file=sys.stderr)
        return 1
    publish_topo = None
    publish_layer = getattr(args, "publish_layer", None)
    if publish_layer:
        if not publish_every:
            print("--publish_layer requires --publish_every_n_batches",
                  file=sys.stderr)
            return 1
        # serve the named PREDICTION layer, not the training cost: the
        # published bundle's feed surface then excludes labels and its
        # output is the prediction /v1/infer clients want
        from paddle_tpu.core.topology import Topology as _Topology

        matches = [l for l in trainer.topology.layers
                   if l.name == publish_layer]
        if not matches:
            print(f"--publish_layer {publish_layer!r}: no such layer in "
                  f"the config (have: "
                  f"{sorted(l.name for l in trainer.topology.layers)})",
                  file=sys.stderr)
            return 1
        publish_topo = _Topology(matches[0])
    # step-granular auto-resume: when step snapshots exist (a previous run
    # crashed or was preempted mid-pass) and the user didn't force a pass
    # boundary with --start_pass, pick up from the newest VALID snapshot
    resume_state = None
    if save_every and save_dir and start_pass == 0:
        found = SGD.load_step_resume(save_dir)
        if found is not None:
            loaded, resume_state = found
            for name in loaded.names():
                if name in params:
                    params.set(name, loaded.get(name))
            logger.info(
                "auto-resume: step snapshot %s (pass %d, batch %d) — "
                "pass --start_pass to override", resume_state["path"],
                resume_state["pass_id"], resume_state["batch_id"])
    if start_pass > 0:
        # resume: load pass-(start_pass-1) checkpoint incl. optimizer
        # state (--start_pass, ParamUtil.h:103-112 — unlike the reference
        # local format, our pass dirs carry the optimizer slots too)
        if not save_dir:
            print("--start_pass requires --save_dir (where pass dirs "
                  "live)", file=sys.stderr)
            return 1
        loaded, opt_state, meta = checkpoint.load_pass(save_dir,
                                                       start_pass - 1)
        for name in loaded.names():
            if name in params:
                params.set(name, loaded.get(name))
        if opt_state is not None:
            trainer._opt_state = opt_state
        logger.info("resumed from pass %d checkpoint (%s)", start_pass - 1,
                    save_dir)

    def handler(ev):
        from paddle_tpu.trainer import event as v2_event

        if isinstance(ev, v2_event.EndPass):
            logger.info("Pass %d done. %s", ev.pass_id,
                        " ".join(f"{k}={v:.5f}" for k, v in ev.metrics.items()))
            period = FLAGS.get("saving_period", 1) or 1
            # the final pass always checkpoints (otherwise num_passes not a
            # multiple of saving_period silently drops the finished model)
            if save_dir and ((ev.pass_id + 1) % period == 0
                             or ev.pass_id == args.num_passes - 1):
                if save_client is not None:
                    try:
                        elected = save_client.request_save_model(
                            trainer_id,
                            getattr(args, "save_block_dur", 60.0))
                    except (ConnectionError, OSError) as e:
                        # a dead master must not lose the trained model:
                        # save anyway (worst case is a redundant write of
                        # identical params, not a lost checkpoint)
                        logger.warning("pass %d: save election "
                                       "unavailable (%s); saving anyway",
                                       ev.pass_id, e)
                        elected = True
                    if not elected:
                        logger.info("pass %d: another trainer holds the "
                                    "save lease; skipping snapshot",
                                    ev.pass_id)
                        return
                checkpoint.save_pass(save_dir, ev.pass_id, trainer.parameters,
                                     trainer._opt_state)
        elif isinstance(ev, v2_event.TestResult):
            logger.info("Test cost=%.6f %s", ev.cost,
                        " ".join(f"{k}={v:.5f}" for k, v in ev.metrics.items()))

    train_stream = reader_mod.batch(train_reader, batch_size)
    if save_every and not getattr(train_stream, "task_queue_backed", False):
        # resumable position tracking (outermost, batch granularity); with
        # a master-attached stream the task queue IS the durable position
        from paddle_tpu.reader.decorator import checkpointable

        train_stream = checkpointable(train_stream,
                                      seed=FLAGS.get("seed", 1))

    # preemption (SIGTERM from a scheduler reclaiming the VM, or Ctrl-C):
    # snapshot at the next batch boundary, then exit cleanly — the
    # restarted process auto-resumes from that snapshot
    preempt = None
    if save_every:
        import signal
        import threading

        preempt = threading.Event()

        def _on_preempt(signum, _frame):
            logger.warning("signal %d: will snapshot at the next batch "
                           "boundary and exit", signum)
            preempt.set()

        for signum in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(signum, _on_preempt)
            except (ValueError, OSError):
                pass  # non-main thread (embedded use): no handler

    trainer.train(
        reader=train_stream,
        num_passes=args.num_passes,
        event_handler=handler,
        feeding=feeding,
        test_reader=(reader_mod.batch(test_reader, batch_size)
                     if test_reader else None),
        start_pass=start_pass,
        save_every_n_batches=save_every,
        snapshot_dir=save_dir if save_every else None,
        resume_state=resume_state,
        preempt_event=preempt,
        keep_snapshots=getattr(args, "keep_step_snapshots", 3),
        publish_every_n_batches=publish_every,
        publish_dir=publish_dir,
        publish_url=getattr(args, "publish_url", None),
        publish_topology=publish_topo)
    if getattr(trainer, "preempted", False):
        logger.warning("training preempted; resume by re-running the same "
                       "command (auto-resume picks up the step snapshot)")
    return 0


def cmd_merge_model(args):
    """paddle merge_model --model_dir/--model_tar --config --output:
    bundle serialized topology + parameters into one inference file
    (MergeModel.cpp:23-64 analog)."""
    from paddle_tpu.io.merged_model import merge_model

    merge_model(config=args.config, config_args=args.config_args or "",
                param_tar=args.model_tar, pass_dir=args.model_dir,
                output=args.output, export_seq_len=args.export_seq_len,
                export_static_batch=args.export_static_batch,
                export_slots=args.export_slots,
                export_batch_ladder=args.export_batch_ladder,
                bundle_version=args.bundle_version,
                quantize=args.quantize,
                host_sidecar=not args.no_host_sidecar,
                export_host_rows=args.export_host_rows)
    print(f"merged model written to {args.output}")
    return 0


def cmd_master(args):
    """Serve the fault-tolerant master task-queue (go/master analog,
    native/master.cc) until interrupted."""
    from paddle_tpu.native import master_serve

    master_serve(port=args.port, snapshot=args.snapshot,
                 task_timeout=args.task_timeout,
                 failure_limit=args.failure_limit,
                 discovery_root=args.discovery_root,
                 advertise_addr=args.advertise_addr)
    return 0


def cmd_pserver(args):
    print("paddle_tpu has no parameter server: distributed training uses "
          "XLA collectives over the device mesh (see paddle_tpu.parallel). "
          "For the task-queue service run `paddle master`.", file=sys.stderr)
    return 1


def build_parser():
    p = argparse.ArgumentParser(prog="paddle",
                                description="PaddleTPU command line")
    sub = p.add_subparsers(dest="cmd")

    t = sub.add_parser("train", help="train a model from a config file")
    t.add_argument("--config", required=True)
    t.add_argument("--job", default="train",
                   choices=["train", "test", "checkgrad", "time"],
                   help="train (default), test (evaluate a saved model), "
                        "checkgrad (finite-difference the whole net), or "
                        "time (forward / forward-backward ms per batch "
                        "over log_period iterations, TrainerMain.cpp:58)")
    t.add_argument("--checkgrad_eps", type=float, default=1e-4,
                   help="finite-difference step for --job=checkgrad")
    t.add_argument("--config_args", default="")
    t.add_argument("--num_passes", type=int, default=1)
    t.add_argument("--start_pass", type=int, default=0,
                   help="resume from save_dir/pass-(N-1) checkpoint "
                        "(params + optimizer state)")
    t.add_argument("--save_dir", default=None)
    t.add_argument("--master_addr", default=None,
                   help="host:port of the task-queue master; enables "
                        "elected model save (exactly one trainer "
                        "snapshots per election window)")
    t.add_argument("--trainer_id", default=None,
                   help="stable id for the save election "
                        "(default: trainer-<pid>)")
    t.add_argument("--save_block_dur", type=float, default=60.0,
                   help="save-lease duration in seconds "
                        "(RequestSaveModel BlockDur)")
    t.add_argument("--init_model_path", default=None)
    t.add_argument("--batch_size", type=int, default=None)
    t.add_argument("--use_bf16", action="store_true",
                   help="bf16 compute with fp32 master weights")
    t.add_argument("--log_period", type=int, default=None)
    t.add_argument("--test_period", type=int, default=None,
                   help="batches between mid-pass test runs (0 = per pass)")
    t.add_argument("--show_parameter_stats_period", type=int, default=None)
    t.add_argument("--saving_period", type=int, default=None,
                   help="passes between checkpoints (with --save_dir)")
    t.add_argument("--save_every_n_batches", type=int, default=0,
                   help="mid-pass step snapshots every N batches (crash-"
                        "safe resume; requires --save_dir). SIGTERM/SIGINT "
                        "snapshot-then-exit, and a rerun auto-resumes from "
                        "the newest valid snapshot")
    t.add_argument("--keep_step_snapshots", type=int, default=3,
                   help="step snapshots retained (older pruned)")
    t.add_argument("--publish_every_n_batches", type=int, default=0,
                   help="continuous train->serve publishing: every N "
                        "batches write a validated, versioned serving "
                        "bundle into --publish_dir and hot-swap the "
                        "daemon (validation gate, bounded retry, "
                        "automatic rollback — docs/serving.md "
                        "'Continuous publishing')")
    t.add_argument("--publish_dir", default=None,
                   help="publish dir: versioned bundle-v*.ptpu files, "
                        "the BUNDLE_VERSION counter and the "
                        "current.ptpu symlink live here")
    t.add_argument("--publish_url", default=None,
                   help="serving daemon base URL (http://host:port): "
                        "publishes notify POST /v1/reload and confirm "
                        "paddle_serving_param_version advanced; omit "
                        "for symlink-flip-only publishing")
    t.add_argument("--publish_layer", default=None,
                   help="layer NAME to publish as the bundle's output "
                        "(the prediction layer /v1/infer clients want; "
                        "default: the full training topology, whose "
                        "feed surface includes labels and whose output "
                        "is the cost)")
    t.add_argument("--pipeline_depth", type=int, default=None,
                   help="train-loop software pipeline depth (default 2): "
                        "overlap host read/feed/H2D of batch N+1 with the "
                        "device compute of batch N; events/snapshots drain "
                        "in exact batch order. 0/1 = strictly synchronous "
                        "(docs/pipeline.md)")
    t.add_argument("--pack_sequences", action="store_true",
                   help="pack several ragged samples per feed row with "
                        "segment ids: deletes padding waste from the hot "
                        "loop while keeping the padded path's loss/"
                        "evaluator trajectory (docs/packing.md)")
    t.add_argument("--pack_max_len", type=int, default=None,
                   help="packed row capacity T (constant feed shape "
                        "across batches; default auto: 2x the batch's "
                        "longest sample, bucketed)")
    t.add_argument("--bucket_rounding", type=int, default=None,
                   help="pad sequence length to a multiple of N instead "
                        "of the next power of two (bounds per-batch "
                        "waste at N-1 steps; default power-of-two)")
    t.add_argument("--host_table_min_rows", type=int, default=None,
                   help="train sparse_update tables with at least this "
                        "many rows HOST-resident: host-RAM row store + "
                        "per-batch device row cache + async sparse-grad "
                        "flush — tables larger than HBM become trainable "
                        "(docs/embedding_cache.md)")
    t.add_argument("--host_cache_rows", type=int, default=None,
                   help="device row-cache capacity per host-resident "
                        "table (rows; default auto-sized power-of-two "
                        "bucket of the batch's unique-id count)")
    t.add_argument("--metrics_port", type=int, default=None,
                   help="serve /metrics (Prometheus text), /metrics.json, "
                        "/healthz and /trace on this port (0 = ephemeral; "
                        "omit to disable — the default)")
    t.add_argument("--trace_dir", default=None,
                   help="collect host trace spans and write Chrome "
                        "trace-event JSON (Perfetto-loadable) here at exit")
    t.add_argument("--metrics_interval", type=float, default=0.0,
                   help="seconds between JSON metric snapshots appended to "
                        "<trace_dir or .>/metrics.jsonl — the headless-CI "
                        "exporter (0 = off)")
    t.set_defaults(fn=cmd_train)

    m = sub.add_parser("merge_model", help="bundle config+params for inference")
    m.add_argument("--config", required=True)
    m.add_argument("--config_args", default="")
    m.add_argument("--model_tar", default=None)
    m.add_argument("--model_dir", default=None)
    m.add_argument("--output", required=True)
    m.add_argument("--export_seq_len", type=int, default=None,
                   help="static sequence length the StableHLO export "
                        "pads masked sequence feeds to (default 16; "
                        "docs/serving.md)")
    m.add_argument("--export_static_batch", type=int, default=None,
                   help="static batch of the C-servable modules "
                        "(default 8)")
    m.add_argument("--export_slots", type=int, default=None,
                   help="static decode-slot batch of the per-tick step "
                        "modules generation bundles export (default 8; "
                        "the daemon's continuous-batching slot array "
                        "runs at exactly this width — docs/serving.md "
                        "\"Step-module bundles\")")
    m.add_argument("--export_batch_ladder", default=None,
                   help="comma list of extra static batch sizes to "
                        "export batch-monomorphic StableHLO modules at "
                        "(e.g. 1,2,4): the serving daemon's infer "
                        "micro-batcher executes a coalesced window at "
                        "the smallest rung that fits — the r11 "
                        "bucket_rounding idiom applied to serving "
                        "(docs/serving.md \"Infer micro-batching\")")
    m.add_argument("--bundle_version", type=int, default=None,
                   help="explicit meta.bundle_version (e.g. a trainer "
                        "step); default is a monotonic ms timestamp — "
                        "the serving daemon exposes the live value as "
                        "paddle_serving_param_version and /v1/reload "
                        "hot-swaps to a new one (docs/serving.md)")
    m.add_argument("--no_host_sidecar", action="store_true",
                   help="skip the __hostrows__ row sidecar for "
                        "host-resident tables: the bundle writes without "
                        "the table and records the refusal in "
                        "meta.stablehlo_skip_reason (docs/serving.md "
                        "\"Host-backed tables\")")
    m.add_argument("--export_host_rows", type=int, default=None,
                   help="staged-rows budget R of the host-table StableHLO "
                        "export (the [R, D] staged-rows module input); "
                        "default is the worst case — every id the claimed "
                        "feeds carry at the largest exported batch")
    m.add_argument("--quantize", choices=("bf16", "int8"), default=None,
                   help="post-training quantization: fc weights + "
                        "embedding tables drop to bf16 (straight cast) "
                        "or int8 (per-channel symmetric, f32 ':scale' "
                        "sidecars) in the tar and every exported "
                        "StableHLO module; biases stay f32 "
                        "(docs/serving.md \"Quantized bundles\")")
    m.set_defaults(fn=cmd_merge_model)

    ms = sub.add_parser("master", help="serve the task-queue master")
    ms.add_argument("--port", type=int, default=7164)
    ms.add_argument("--snapshot", default=None)
    ms.add_argument("--task_timeout", type=float, default=60.0)
    ms.add_argument("--failure_limit", type=int, default=3)
    ms.add_argument("--discovery_root", default=None,
                    help="shared dir for leader election + address "
                         "publication (etcd analog)")
    ms.add_argument("--advertise_addr", default=None,
                    help="address to publish in discovery (default: "
                         "routable local IP)")
    ms.set_defaults(fn=cmd_master)

    ps = sub.add_parser("pserver", help="(collectives replace the pserver)")
    ps.set_defaults(fn=cmd_pserver)

    # NOTE: cluster_train is dispatched in main() BEFORE argparse — a
    # REMAINDER positional cannot capture its leading --hosts flag. The
    # subparser exists only so `paddle --help` lists the command.
    sub.add_parser("cluster_train",
                   help="fan a command out over a host list "
                        "(cluster_train/paddle.py analog): paddle "
                        "cluster_train --hosts a,b -- <cmd...>")

    v = sub.add_parser("version", help="print version info")
    v.set_defaults(fn=cmd_version)
    return p


def main(argv=None):
    # chaos bootstrap: a scripted fault plan named by $PADDLE_TPU_FAULT_PLAN
    # installs before any subcommand runs, so multiprocess chaos tests can
    # script a CLI child's demise deterministically
    from paddle_tpu.distributed import faults as _faults

    _faults.install_from_env()
    import paddle_tpu

    paddle_tpu.compile_cache()
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    if argv[:1] == ["cluster_train"]:
        # forwarded verbatim: the launcher owns its own flags and the
        # post-`--` command must pass through untouched
        from paddle_tpu.distributed.cluster_launch import main as cluster_main

        return cluster_main(argv[1:])
    p = build_parser()
    args = p.parse_args(argv)
    if not getattr(args, "fn", None):
        p.print_help()
        return 1
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
