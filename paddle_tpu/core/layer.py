"""Layer graph nodes + the layer-type registry.

TPU-native analog of the reference's layer machinery:
- ``REGISTER_LAYER`` string->factory registry (paddle/gserver/layers/Layer.h:31)
  becomes ``register_layer`` filling LAYER_REGISTRY with LayerDefs;
- a ``Layer`` here is a *graph node* (like the v2 API's LayerOutput /
  config_parser LayerConfig), not a stateful object: all state lives in the
  parameters pytree and all compute is a pure ``forward`` function, so the
  whole network compiles into one XLA program instead of per-layer virtual
  calls (NeuralNetwork.cpp:235-295).

Each LayerDef supplies:
  infer(cfg, in_infos)   -> ArgInfo        (output size/shape, like the config
                                            parser's per-layer size computation)
  params(cfg, in_infos)  -> {suffix: ParamSpec}
  forward(cfg, params, ins, ctx) -> Arg    (pure, jit-traceable)
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from paddle_tpu.attr import ExtraAttr, ParamAttr, to_param_attr
from paddle_tpu.core.arg import Arg, ArgInfo
from paddle_tpu.utils.error import enforce
from paddle_tpu.utils.registry import Registry


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one learnable array of a layer."""

    shape: Tuple[int, ...]
    attr: ParamAttr
    fan_in: int = 1
    is_bias: bool = False
    dtype: Any = jnp.float32


class ForwardContext:
    """Per-trace context passed to every layer forward.

    Carries: training flag, a deterministic per-layer RNG derivation (for
    dropout / sampling layers), and a scratch dict for cross-layer plumbing
    (recurrent memories, get_output taps) — the functional replacement of
    gserver's LayerMap/ParameterMap mutable state.
    """

    def __init__(self, training: bool, rng: Optional[jax.Array] = None,
                 mesh=None, outputs: Optional[Dict[str, Arg]] = None,
                 sparse_tangents: Optional[Dict[str, jax.Array]] = None,
                 sparse_collect: Optional[Dict[str, tuple]] = None,
                 packed: bool = False):
        self.training = training
        self._rng = rng
        self.mesh = mesh
        # sequence-packing mode (docs/packing.md): True when the feed
        # batch packs several sequences per row (plain-SEQUENCE feeds
        # carrying seg_ids). Segment-aware layers then cut state/attention
        # at segment boundaries; layers that cannot honor packed rows
        # refuse loudly. Static per trace: packed and unpacked feeds have
        # different pytree structures, so jit caches them separately.
        self.packed = packed
        self.outputs: Dict[str, Arg] = outputs if outputs is not None else {}
        self.extras: Dict[str, Any] = {}
        # sparse-row gradient protocol (layers/misc.py selective_fc;
        # trainer/trainer.py make_train_step):
        # - sparse_collect: discovery trace — sparse-capable layers record
        #   {param_name: (values_shape, dtype)} tangent slots and run
        #   their normal forward;
        # - sparse_tangents: apply trace — {param_name: zero [rows..., D]
        #   array}; the layer adds the slot to its gathered rows and
        #   stop-gradients the table, so jax.grad w.r.t. the slot yields
        #   the per-row dW without ever touching the [C, D] table grad.
        #   Row ids are reported in extras["sparse_rows"][param_name].
        self.sparse_tangents = sparse_tangents
        self.sparse_collect = sparse_collect
        # set by Topology.forward before each layer call: {suffix: pname}
        # so layer impls can map their local "w0"/"wbias" params to global
        # parameter names (the aux_updates mapping, available in-forward)
        self.layer_param_names: Dict[str, str] = {}

    def rng(self, name: str) -> jax.Array:
        import zlib
        enforce(self._rng is not None,
                "this forward needs an rng (dropout/sampling layer present); "
                "pass rng= to Topology.forward / trainer")
        # stable per-layer derivation (not Python hash(): PYTHONHASHSEED
        # randomisation would break run-to-run reproducibility)
        return jax.random.fold_in(self._rng, zlib.crc32(name.encode()) & 0x7FFFFFFF)


@dataclasses.dataclass(frozen=True)
class LayerDef:
    type: str
    infer: Callable[..., ArgInfo]
    forward: Callable[..., Arg]
    params: Optional[Callable[..., Dict[str, ParamSpec]]] = None


LAYER_REGISTRY: Registry = Registry("layer")


def register_layer(type_name: str, infer=None, params=None):
    """Decorator registering a forward fn as a layer type
    (REGISTER_LAYER analog)."""

    def deco(forward_fn):
        LAYER_REGISTRY.register(
            type_name,
            LayerDef(type=type_name, infer=infer or _infer_identity,
                     forward=forward_fn, params=params))
        return forward_fn

    return deco


# Per-step statistics a layer hands out of the jitted step for the operator's
# counters. A forward puts an array under
# ctx.extras["step_stats"][<layer type>][<layer name>]; Topology.loss_fn
# hands the dict out beside the outputs ("#step_stats"), the train loop
# fetches it where it drains the cost and gives each array to the publisher
# its layer type registered here. No core code names a layer type.
STEP_STATS_PUBLISHERS: Dict[str, Callable[[str, Any], None]] = {}


def register_step_stats(type_name: str):
    """Decorator registering publish(layer_name, host array) for the step
    statistics of one layer type."""

    def deco(publish):
        STEP_STATS_PUBLISHERS[type_name] = publish
        return publish

    return deco


def publish_step_stats(stats):
    """{layer type: {layer name: array}} of ONE drained step into the
    publishers' counters (one device_get for all of it)."""
    for type_name, by_layer in jax.device_get(stats).items():
        for lname, value in by_layer.items():
            STEP_STATS_PUBLISHERS[type_name](lname, value)


def _infer_identity(cfg, in_infos):
    enforce(len(in_infos) >= 1, f"layer {cfg.name} needs >=1 input")
    return in_infos[0]


_name_counters = itertools.count()
_name_lock = threading.Lock()

# observers notified on every Layer construction; recurrent-group tracing
# registers one to find memory-target layers that aren't step outputs
creation_hooks: List = []


def _auto_name(type_name: str) -> str:
    with _name_lock:
        return f"__{type_name}_{next(_name_counters)}__"


class layer_name_scope:
    """Deterministic auto-naming scope: inside the scope the counter
    restarts from 0, so re-parsing the same config yields identical layer
    names (the reference config parser numbers layers per config, which is
    what makes a merge_model bundle's names line up with a fresh parse)."""

    def __enter__(self):
        global _name_counters
        with _name_lock:
            self._saved = _name_counters
            _name_counters = itertools.count()
        return self

    def __exit__(self, *a):
        global _name_counters
        with _name_lock:
            _name_counters = self._saved


class Layer:
    """A node in the model graph (v2 LayerOutput analog)."""

    def __init__(self, type: str, inputs: Sequence["Layer"], name: Optional[str] = None,
                 size: Optional[int] = None, act=None,
                 param_attrs: Optional[List[ParamAttr]] = None,
                 bias_attr=None, extra: Optional[ExtraAttr] = None, **cfg):
        from paddle_tpu import activation as _act_mod

        self.type = type
        self.name = name or _auto_name(type)
        self.inputs: List[Layer] = list(inputs)
        self.size = size
        self.act = _act_mod.resolve(act) if act is not None else None
        self.param_attrs = [to_param_attr(a) for a in (param_attrs or [])]
        # bias_attr semantics follow the reference DSL: False = no bias,
        # None/True = default bias, ParamAttr = custom.
        self.bias_attr = bias_attr
        self.extra = extra or ExtraAttr()
        self.cfg: Dict[str, Any] = cfg
        self._def: LayerDef = LAYER_REGISTRY.get(type)
        # reverse-depth for topology extraction
        self.depth = 1 + max((i.depth for i in self.inputs), default=0)
        for hook in creation_hooks:
            hook(self)

    # --- config accessors used by layer implementations -------------------
    def attr(self, key: str, default=None):
        return self.cfg.get(key, default)

    @property
    def scope(self) -> str:
        """The ``jax.named_scope`` the layer graph runs this layer under: its
        ``scope`` attribute (a model's own path, ``qwen3next/l0/mixer``), else
        its name. A profile read by scope
        (``paddle_tpu.observability.profile``) groups device time by it."""
        return self.cfg.get("scope") or self.name

    def param_attr(self, i: int = 0) -> ParamAttr:
        if i < len(self.param_attrs):
            return self.param_attrs[i]
        return ParamAttr()

    def bias_param_attr(self) -> Optional[ParamAttr]:
        if self.bias_attr is False:
            return None
        if self.bias_attr in (None, True):
            return ParamAttr()
        return to_param_attr(self.bias_attr)

    # --- graph protocol ---------------------------------------------------
    def infer(self, in_infos: List[ArgInfo]) -> ArgInfo:
        return self._def.infer(self, in_infos)

    def out_info(self) -> ArgInfo:
        """Inferred output ArgInfo, computed recursively from the graph.

        Single source of truth for output sizes/shapes — model builders
        should query this instead of re-deriving conv/pool arithmetic
        (the reference config parser's size propagation; VERDICT r1 #5).
        Cached: layer graphs are immutable once constructed.
        """
        cached = getattr(self, "_out_info", None)
        if cached is None:
            cached = self.infer([i.out_info() for i in self.inputs])
            self._out_info = cached
        return cached

    def param_specs(self, in_infos: List[ArgInfo]) -> Dict[str, ParamSpec]:
        if self._def.params is None:
            return {}
        return self._def.params(self, in_infos)

    def forward(self, params: Dict[str, jax.Array], ins: List[Arg],
                ctx: ForwardContext) -> Arg:
        out = self._def.forward(self, params, ins, ctx)
        if self.act is not None:
            if self.act.name == "softmax" and \
                    not (self.extra.drop_rate and ctx.training):
                # stash pre-softmax logits: a downstream cross-entropy
                # cost fuses into the stable log-softmax form, and XLA's
                # DCE removes the softmax when the probs then have no
                # other consumer (layers/cost.py _xent_forward) — the
                # softmax_with_cross_entropy_op fusion without a graph
                # rewrite. Costs nothing when unused (dead code). Guard
                # matches the dropout application below: an applied
                # dropout between softmax and cost must block fusion.
                # '#' keeps the key outside get_output()'s ':' namespace.
                ctx.extras[f"{self.name}#logits"] = out
            out = out.with_value(self.act.apply(out.value, out.mask))
        if self.extra.drop_rate and ctx.training:
            keep = 1.0 - self.extra.drop_rate
            key = ctx.rng(self.name + "/dropout")
            m = jax.random.bernoulli(key, keep, out.value.shape)
            out = out.with_value(jnp.where(m, out.value / keep, 0.0))
        return out

    def __repr__(self):
        return f"<Layer {self.name} type={self.type} size={self.size}>"

    # Layer arithmetic sugar (v2 API / trainer_config_helpers layer_math:
    # python/paddle/trainer_config_helpers/math.py operator overloads)
    def __add__(self, other) -> "Layer":
        from paddle_tpu.layer import addto, slope_intercept
        if isinstance(other, (int, float)):
            return slope_intercept(input=self, intercept=float(other))
        return addto(input=[self, other])

    def __radd__(self, other) -> "Layer":
        return self.__add__(other)

    def __sub__(self, other) -> "Layer":
        from paddle_tpu.layer import addto, slope_intercept
        if isinstance(other, (int, float)):
            return slope_intercept(input=self, intercept=-float(other))
        return addto(input=[self, slope_intercept(input=other, slope=-1.0)])

    def __rsub__(self, other) -> "Layer":
        from paddle_tpu.layer import slope_intercept
        return slope_intercept(input=self, slope=-1.0) + other

    def __mul__(self, other) -> "Layer":
        from paddle_tpu.layer import slope_intercept
        if isinstance(other, (int, float)):
            return slope_intercept(input=self, slope=float(other))
        return NotImplemented

    def __rmul__(self, other) -> "Layer":
        return self.__mul__(other)

    def __neg__(self) -> "Layer":
        from paddle_tpu.layer import slope_intercept
        return slope_intercept(input=self, slope=-1.0)


def param_name(layer_name: str, suffix: str, attr: ParamAttr) -> str:
    """Reference naming convention: _layer.w0 / _layer.wbias
    (config_parser.py parameter naming)."""
    return attr.name or f"_{layer_name}.{suffix}"
