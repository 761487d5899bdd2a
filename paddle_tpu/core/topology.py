"""Topology: extract the subgraph feeding given outputs and compile it.

Analog of python/paddle/v2/topology.py:26 (subgraph extraction ->
ModelConfig proto) + gserver's NeuralNetwork topological execution
(NeuralNetwork.cpp:235-295) — except "execution" here is tracing a pure
function that XLA compiles end-to-end, and "backward" is jax.grad over it
(the Backward()-as-graph-transform idea of the proto-Fluid engine,
paddle/framework/backward.h:23, realised by autodiff).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from paddle_tpu.core.arg import Arg, ArgInfo, as_arg
from paddle_tpu.core.layer import ForwardContext, Layer, ParamSpec, param_name
from paddle_tpu.initializer import init_array
from paddle_tpu.utils.error import enforce


def topology_from_config(d: dict) -> "Topology":
    """Rebuild a runnable Topology from ``Topology.serialize()`` output
    (the parse-back path the reference gets from its protobuf ModelConfig;
    VERDICT r1 L7 gap). Parameter names are restored by binding explicit
    ParamAttr names wherever the serialized name differs from the default
    ``_<layer>.<suffix>`` convention (shared params like crfw)."""
    from paddle_tpu import data_type as dt
    from paddle_tpu.attr import ParamAttr

    enforce(d.get("format", "").startswith("paddle_tpu.model_config"),
            "not a serialized paddle_tpu model config")
    by_name: Dict[str, Layer] = {}
    for le in d["layers"]:
        cfg = dict(le.get("cfg") or {})
        it = cfg.pop("input_type", None)
        if isinstance(it, dict):
            from paddle_tpu.data_type import InputType, SeqType

            dtype = jnp.int32 if it["kind"] in ("index", "sparse_binary") \
                else jnp.float32
            cfg["input_type"] = InputType(it["dim"], it["seq_type"],
                                          it["kind"], dtype, it.get("max_ids"))
        # JSON turns tuples into lists; shape-ish cfg values must be tuples
        cfg = {k: (tuple(v) if isinstance(v, list) else v)
               for k, v in cfg.items()}
        param_attrs: List[ParamAttr] = []
        bias_attr = None if le.get("bias", True) else False
        for suffix, pname in (le.get("param_names") or {}).items():
            if pname == f"_{le['name']}.{suffix}":
                continue
            if suffix == "wbias":
                bias_attr = ParamAttr(name=pname)
            elif suffix.startswith("w") and suffix[1:].isdigit():
                i = int(suffix[1:])
                while len(param_attrs) <= i:
                    param_attrs.append(ParamAttr())
                param_attrs[i] = ParamAttr(name=pname)
        inputs = [by_name[n] for n in le["inputs"]]
        lay = Layer(le["type"], inputs, name=le["name"], size=le["size"],
                    act=le["act"], param_attrs=param_attrs or None,
                    bias_attr=bias_attr, **cfg)
        by_name[le["name"]] = lay
    return Topology([by_name[n] for n in d["outputs"]])


# layer types whose value comes from feeds, not computation ("data" for the
# outer graph; "step_input"/"memory" inside recurrent groups)
FEED_TYPES = frozenset({"data", "step_input", "memory"})


class Topology:
    def __init__(self, outputs: Union[Layer, Sequence[Layer]],
                 extra_outputs: Optional[Sequence[Layer]] = None):
        if isinstance(outputs, Layer):
            outputs = [outputs]
        self.outputs: List[Layer] = list(outputs) + list(extra_outputs or [])
        self.layers: List[Layer] = self._topo_sort(self.outputs)
        self.layer_map: Dict[str, Layer] = {l.name: l for l in self.layers}
        enforce(len(self.layer_map) == len(self.layers),
                "duplicate layer names in topology")
        self.data_layers: List[Layer] = [l for l in self.layers if l.type == "data"]
        self.feed_layers: List[Layer] = [l for l in self.layers
                                         if l.type in FEED_TYPES]
        self._infos: Dict[str, ArgInfo] = {}
        self._param_specs: Dict[str, ParamSpec] = {}
        self._param_owner: Dict[str, str] = {}
        self._layer_params: Dict[str, Dict[str, str]] = {}
        self._infer_all()

    @staticmethod
    def _topo_sort(outputs: Sequence[Layer]) -> List[Layer]:
        """DFS from outputs (the v2 __get_used_layers__ analog,
        python/paddle/v2/layer.py:110); post-order = valid topo order."""
        seen, order = set(), []

        def visit(l: Layer):
            if id(l) in seen:
                return
            seen.add(id(l))
            for i in l.inputs:
                visit(i)
            order.append(l)

        for o in outputs:
            visit(o)
        return order

    def _infer_all(self):
        for l in self.layers:
            in_infos = [self._infos[i.name] for i in l.inputs]
            self._infos[l.name] = l.infer(in_infos)
            specs = l.param_specs(in_infos)
            self._layer_params[l.name] = {}
            for suffix, spec in specs.items():
                pname = param_name(l.name, suffix, spec.attr)
                self._layer_params[l.name][suffix] = pname
                if pname in self._param_specs:
                    # shared parameter (is_shared / same ParamAttr.name):
                    # shapes must agree (reference shared-parameter semantics)
                    enforce(self._param_specs[pname].shape == spec.shape,
                            f"shared parameter {pname} shape mismatch: "
                            f"{self._param_specs[pname].shape} vs {spec.shape}")
                else:
                    self._param_specs[pname] = spec
                    self._param_owner[pname] = l.name

    # --- public query ----------------------------------------------------
    def info(self, layer: Union[str, Layer]) -> ArgInfo:
        name = layer if isinstance(layer, str) else layer.name
        return self._infos[name]

    def param_specs(self) -> Dict[str, ParamSpec]:
        return dict(self._param_specs)

    def layer_param_map(self, layer_name: str) -> Dict[str, str]:
        """{param suffix: full parameter name} for one layer — the
        mapping :meth:`forward` uses to slice the global params dict
        into a layer's ``lparams`` (the decode step export drives a
        single layer's forward pieces directly and needs the same
        slice)."""
        return dict(self._layer_params[layer_name])

    def data_type(self):
        """[(name, InputType-or-ArgInfo)] for data layers — DataFeeder uses
        this (v2 Topology.data_type analog). Returns the user's original
        InputType when the data layer declared one (feeder needs kind/
        seq_type), else the inferred ArgInfo."""
        out = []
        for l in self.data_layers:
            itype = l.attr("input_type")
            out.append((l.name, itype if itype is not None else self._infos[l.name]))
        return out

    def _feeds_packed(self, feeds) -> bool:
        """True when the feed batch is sequence-PACKED (docs/packing.md):
        a plain-SEQUENCE data layer whose feed carries seg_ids. Nested
        (SUB_SEQUENCE) inputs also carry seg_ids but mark sub-sequences
        of ONE sample, not packing — they are excluded here, so nested
        models keep their pre-packing behavior bit for bit."""
        from paddle_tpu.data_type import InputType, SeqType

        for l in self.data_layers:
            it = l.attr("input_type")
            if isinstance(it, InputType) \
                    and it.seq_type == SeqType.SUB_SEQUENCE:
                continue
            a = feeds.get(l.name)
            if isinstance(a, Arg) and a.mask is not None \
                    and a.seg_ids is not None:
                return True
        return False

    # --- compile ----------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Dict[str, jax.Array]:
        """Materialize every parameter EXCEPT host-resident tables
        (ParamAttr(host_resident=True), docs/embedding_cache.md): those
        live in a HostRowStore and may be too large to ever exist as one
        array — their rows materialize lazily host-side. Skipping keeps
        the per-parameter fold_in indices of the remaining params
        unchanged, so non-host params init bit-identically either way."""
        params = {}
        for i, (pname, spec) in enumerate(sorted(self._param_specs.items())):
            if getattr(spec.attr, "host_resident", False):
                continue
            key = jax.random.fold_in(rng, i)
            params[pname] = init_array(key, spec.shape, spec.attr, spec.fan_in,
                                       spec.dtype, spec.is_bias)
        return params

    def host_param_names(self, min_rows: int = 0) -> List[str]:
        """Names of tables selected for host-resident training: explicit
        ``ParamAttr(host_resident=True)`` opt-ins, plus (when
        ``min_rows > 0``) any sparse_update table with at least that
        many rows — the size-threshold selection of
        SGD.train(host_table_min_rows=...)."""
        out = []
        for pname, spec in sorted(self._param_specs.items()):
            if getattr(spec.attr, "host_resident", False) or (
                    min_rows and spec.attr.sparse_update
                    and len(spec.shape) >= 1 and spec.shape[0] >= min_rows):
                out.append(pname)
        return out

    def host_table_feeds(self, pnames: Sequence[str]) -> Dict[str, List[str]]:
        """{table param name: [data-layer feed names]} for host-resident
        tables: the id feeds the HostTableRuntime remaps into cache-slot
        space. Every consumer of a host table must be an embedding
        lookup fed DIRECTLY by a data layer — the only pattern whose ids
        are visible host-side before dispatch (anything else would need
        the ids computed inside the compiled step, where the table no
        longer exists)."""
        out: Dict[str, List[str]] = {p: [] for p in pnames}
        for l in self.layers:
            for suffix, pname in self._layer_params[l.name].items():
                if pname not in out:
                    continue
                enforce(l.type == "embedding",
                        f"host-resident table {pname!r} is consumed by "
                        f"{l.type!r} layer {l.name!r}; only embedding "
                        "lookups over data-layer ids can train "
                        "host-resident (docs/embedding_cache.md)")
                src = l.inputs[0]
                enforce(src.type == "data",
                        f"host-resident table {pname!r}: embedding "
                        f"{l.name!r} must consume a data layer directly "
                        f"(got {src.type!r} {src.name!r}) so the touched "
                        "ids are known host-side before dispatch")
                if src.name not in out[pname]:
                    out[pname].append(src.name)
        for pname, feeds in out.items():
            enforce(feeds, f"host-resident table {pname!r} has no "
                    "embedding consumer in this topology")
        # the runtime rewrites each claimed feed into cache-slot space
        # GLOBALLY, so a feed shared with any other consumer (a second
        # table, an fc, an HBM embedding) would silently hand that
        # consumer slot indices instead of ids — refuse
        claimed: Dict[str, str] = {}
        for pname, feeds in out.items():
            for fn in feeds:
                other = claimed.setdefault(fn, pname)
                enforce(other == pname,
                        f"data layer {fn!r} feeds two host-resident "
                        f"tables ({other!r} and {pname!r}); the "
                        "cache-slot remap of one would corrupt the "
                        "other's ids — give each table its own id feed")
        for l in self.layers:
            for src in l.inputs:
                fn = getattr(src, "name", None)
                if fn not in claimed:
                    continue
                pname = claimed[fn]
                lparams = set(self._layer_params.get(l.name, {}).values())
                enforce(l.type == "embedding" and pname in lparams,
                        f"data layer {fn!r} is remapped into cache-slot "
                        f"space for host-resident table {pname!r} but is "
                        f"also consumed by {l.type!r} layer {l.name!r}; "
                        "the slot ids would silently corrupt that "
                        "consumer — give the host table its own id feed "
                        "(docs/embedding_cache.md)")
        return out

    def forward(self, params: Dict[str, jax.Array], feeds: Dict[str, object],
                training: bool = False, rng: Optional[jax.Array] = None,
                mesh=None, return_ctx: bool = False,
                sparse_tangents=None, sparse_collect=None):
        """Run every layer once in topological order. Pure and jittable.

        feeds: {data_layer_name: Arg | array | (value, mask)}.
        Returns every layer's output Arg keyed by layer name (plus the
        ForwardContext when return_ctx, for aux state like BN batch stats).

        sparse_tangents / sparse_collect: the sparse-row gradient protocol
        (see ForwardContext; produced and consumed by make_train_step).
        """
        ctx = ForwardContext(training=training, rng=rng, mesh=mesh,
                             sparse_tangents=sparse_tangents,
                             sparse_collect=sparse_collect,
                             packed=self._feeds_packed(feeds))
        for l in self.layers:
            if l.type in FEED_TYPES:
                enforce(l.name in feeds, f"missing feed for data layer {l.name!r}")
                ctx.outputs[l.name] = as_arg(feeds[l.name])
                continue
            lparams = {suffix: params[pname]
                       for suffix, pname in self._layer_params[l.name].items()}
            ctx.layer_param_names = self._layer_params[l.name]
            ins = [ctx.outputs[i.name] for i in l.inputs]
            try:
                # the one site that names a layer's ops: metadata only (an
                # op's `op_name`), so the jaxpr, the lowered text without
                # debug info and the compile-cache key are what they were
                with jax.named_scope(l.scope):
                    ctx.outputs[l.name] = l.forward(lparams, ins, ctx)
            except Exception as e:
                # CustomStackTrace analog (paddle/utils/CustomStackTrace.h:26,
                # NeuralNetwork.cpp:244-293): say where in the MODEL we died,
                # not just where in the library
                note = (f"while computing layer {l.name!r} "
                        f"(type {l.type!r}, inputs "
                        f"{[i.name for i in l.inputs]})")
                if hasattr(e, "add_note"):       # PEP 678 (3.11+)
                    e.add_note(note)
                else:
                    # pre-3.11: set the PEP 678 attribute directly so
                    # callers reading __notes__ see the same context
                    e.__notes__ = [*getattr(e, "__notes__", []), note]
                raise
        if return_ctx:
            return ctx.outputs, ctx
        return ctx.outputs

    def aux_updates(self, ctx) -> Dict[str, jax.Array]:
        """Aux (non-gradient) parameter updates collected during forward —
        batch-norm moving stats (the reference keeps these in static
        Parameter slots updated in-place; here they're explicit outputs of
        the jitted step)."""
        updates = {}
        for lname, stats in ctx.extras.get("batch_stats", {}).items():
            for suffix, val in stats.items():
                pname = self._layer_params[lname].get(suffix)
                if pname is not None:
                    updates[pname] = val
        return updates

    def static_map(self) -> Dict[str, bool]:
        """Which parameters are frozen w.r.t. gradients (is_static /
        moving stats)."""
        return {n: s.attr.is_static for n, s in self._param_specs.items()}

    def lr_mults(self) -> Dict[str, float]:
        return {n: s.attr.learning_rate for n, s in self._param_specs.items()
                if s.attr.learning_rate != 1.0}

    def loss_fn(self, cost_layer: Optional[Union[str, Layer]] = None,
                compute_dtype=None):
        """Build loss(params, feeds, rng) -> (scalar, outputs) for training.
        Cost = sum over output cost layers (TrainerInternal.cpp:137
        Argument::sum analog).

        compute_dtype (e.g. jnp.bfloat16) enables mixed precision: float32
        params and feeds are cast to it before the forward, so matmuls/convs
        run on the MXU in bf16 while the caller keeps fp32 master weights
        (grads flow back to fp32 through the cast's vjp). Static params
        (batch-norm moving stats) stay fp32; cost layers upcast internally.
        """
        cost_names = None
        if cost_layer is not None:
            cost_names = [cost_layer if isinstance(cost_layer, str) else cost_layer.name]
        else:
            cost_names = [o.name for o in self.outputs]
        static = self.static_map()

        def cast_arg(a):
            a = as_arg(a)
            v = a.value
            if jnp.issubdtype(v.dtype, jnp.floating) and v.dtype != compute_dtype:
                v = v.astype(compute_dtype)
            # masks stay fp32: they feed length sums (mask.sum) and pooling
            # denominators, and bf16 cannot represent integers > 256 —
            # layers cast them to the value dtype locally where they only
            # gate/blend values
            return Arg(v, a.mask, a.seg_ids)

        def loss(params, feeds, rng=None, training=True, mesh=None,
                 sparse_tangents=None, sparse_collect=None):
            if compute_dtype is not None:
                # the casts belong to no layer: a scope of their own, so a
                # profile read by scope does not file them under `(none)`
                with jax.named_scope("precision_cast"):
                    params = {k: (v.astype(compute_dtype)
                                  if v.dtype == jnp.float32
                                  and not static.get(k) else v)
                              for k, v in params.items()}
                    feeds = {k: cast_arg(v) for k, v in feeds.items()}
            outs, ctx = self.forward(params, feeds, training=training, rng=rng,
                                     mesh=mesh, return_ctx=True,
                                     sparse_tangents=sparse_tangents,
                                     sparse_collect=sparse_collect)
            total = jnp.float32(0.0)
            for cn in cost_names:
                v = outs[cn].value
                # packed feeds: each row's cost sums several sequences,
                # so "mean over batch" divides by the SEQUENCE count the
                # cost layer published (register_cost), not the row count
                # — the packed loss then matches the unpacked loss over
                # the same samples. Unpacked: extras key absent, graph
                # unchanged.
                n_seq = ctx.extras.get(f"{cn}#n_seq")
                if n_seq is not None:
                    total = total + jnp.sum(v) / jnp.maximum(n_seq, 1.0)
                else:
                    total = total + jnp.sum(v) / v.shape[0]  # mean over batch
            aux = self.aux_updates(ctx)
            if "step_stats" in ctx.extras:
                # what layers hand to the operator's counters
                # (core/layer.py register_step_stats); '#' keeps the key
                # out of the layer-name space, the train step hands it
                # out beside its metrics
                outs = {**outs, "#step_stats": ctx.extras["step_stats"]}
            if sparse_tangents is not None:
                # reserved key popped by make_train_step; only present when
                # the caller opted into the sparse-grad protocol, so plain
                # aux consumers (async updater, checkgrad) never see it
                aux["__sparse_rows__"] = ctx.extras.get("sparse_rows", {})
            return total, (outs, aux)

        # make_train_step skips sparse-slot discovery entirely for models
        # with no sparse_update parameters (no second trace at compile)
        loss._sparse_capable = any(
            s.attr.sparse_update for s in self._param_specs.values())
        # the trainer's evaluator harness keys packed-aware counting on
        # this (trace-time structure check, same one forward uses for
        # ctx.packed): seg_ids presence alone cannot distinguish packed
        # rows from nested SUB_SEQUENCE feeds, and nested models must
        # keep their pre-packing evaluator behavior bit for bit
        loss._feeds_packed = self._feeds_packed
        return loss

    def serialize(self) -> dict:
        """JSON-able model config (ModelConfig proto analog) for
        checkpoint bundles / merged inference models (MergeModel.cpp).
        Round-trips through ``topology_from_config`` — data-layer input
        types and parameter-name bindings are preserved so a deserialized
        topology feeds and forwards identically."""
        def act_name(a):
            return a.name if a is not None else None

        def layer_entry(l: Layer) -> dict:
            cfg = {k: v for k, v in l.cfg.items()
                   if isinstance(v, (int, float, str, bool, list, tuple,
                                     type(None)))}
            it = l.cfg.get("input_type")
            if it is not None:
                cfg["input_type"] = {"dim": it.dim, "seq_type": it.seq_type,
                                     "kind": it.kind,
                                     "max_ids": it.max_ids}
            return {"name": l.name, "type": l.type, "size": l.size,
                    "inputs": [i.name for i in l.inputs],
                    "act": act_name(l.act),
                    "bias": (False if l.bias_attr is False else True),
                    "param_names": dict(self._layer_params[l.name]),
                    "cfg": cfg}

        return {
            "format": "paddle_tpu.model_config.v1",
            "layers": [layer_entry(l) for l in self.layers],
            "outputs": [o.name for o in self.outputs],
            "params": {n: {"shape": list(s.shape), "is_bias": s.is_bias,
                           "is_static": s.attr.is_static}
                       for n, s in self._param_specs.items()},
        }
