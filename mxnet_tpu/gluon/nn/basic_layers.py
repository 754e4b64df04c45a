"""Gluon basic layers (reference: python/mxnet/gluon/nn/basic_layers.py)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ... import autograd
from ...base import MXNetError
from ...ndarray.ndarray import NDArray, _apply
from ...ops import nn_ops as K
from ..block import (Block, HybridBlock, _layer_rng, _report_aux_update,
                     is_symbolic)

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "Flatten",
           "Lambda", "HybridLambda", "Embedding", "ShardedEmbedding",
           "ShardedMoE", "BatchNorm", "LayerNorm",
           "InstanceNorm", "GroupNorm", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "Swish", "GELU", "SiLU", "Concurrent", "Identity", "BatchNormReLU"]


class _SequentialContainer:
    """Shared container behaviour for Sequential / HybridSequential."""

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        items = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            for b in items[key]:
                net.register_child(b)
            return net
        return items[key]

    def __iter__(self):
        return iter(self._children.values())


class Sequential(_SequentialContainer, Block):
    """Stack of Blocks executed in order."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)


class HybridSequential(_SequentialContainer, HybridBlock):
    """Stack of HybridBlocks — hybridizes into one XLA executable."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        for block in self._children.values():
            x = block(x)
        return x


class Dense(HybridBlock):
    """Fully-connected layer y = act(x W^T + b) (reference: nn.Dense)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype=np.float32, weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._flatten = flatten
        self._activation = activation
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), dtype=dtype,
                    init=bias_initializer, allow_deferred_init=True)
            else:
                self.bias = None

    def _infer_shapes(self, x):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight._finish_deferred_init((self._units, in_units))
        if self.bias is not None:
            self.bias._finish_deferred_init((self._units,))

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, no_bias=bias is None,
                               num_hidden=self._units, flatten=self._flatten)
        if self._activation is not None:
            out = F.Activation(out, act_type=self._activation)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (f"Dense({shape[1] if shape and len(shape) > 1 else None} -> "
                f"{self._units}, "
                f"{'linear' if not self._activation else self._activation})")


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if not autograd.is_training() or self._rate <= 0:
            return x
        key = _layer_rng()
        return _apply(lambda a, _key=key, _p=self._rate, _axes=self._axes:
                      K.dropout(a, _key, _p, True, _axes), [x])

    def __repr__(self):
        return f"Dropout(p = {self._rate}, axes={self._axes})"


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return x.flatten()

    def __repr__(self):
        return "Flatten"


class Identity(HybridBlock):
    def hybrid_forward(self, F, x):
        return x


class Lambda(Block):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            from ... import ndarray as F
            function_ = getattr(F, function)
            self._func = lambda *a: function_(*a)
        else:
            self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            name = function
            self._func = lambda F, *a: getattr(F, name)(*a)
        else:
            self._func = function

    def hybrid_forward(self, F, *args):
        return self._func(F, *args)


class Embedding(HybridBlock):
    def __init__(self, input_dim, output_dim, dtype=np.float32,
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer)

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)

    def cast(self, dtype):
        # the block's dtype governs the TABLE only; integer index batches
        # must never be cast through a float dtype (exactness dies at
        # 2**24 — ISSUE 15 satellite). HybridBlock.cast already touches
        # parameters only; this override just documents + pins that.
        return super().cast(dtype)

    def __repr__(self):
        return f"Embedding({self._input_dim} -> {self._output_dim})"


class ShardedEmbedding(Embedding):
    """Model-parallel embedding table for recommender-scale vocabularies
    (ISSUE 15; docs/PERFORMANCE.md "Sharded embeddings").

    Same forward contract as `Embedding`, but the table is meant to be
    ROW-SHARDED over a mesh axis by a shard-plan rule
    (`shard.DEFAULT_RULES` row-shards ``*embed*_weight`` over ``tp``),
    and under a captured step (`Trainer.capture` with `Trainer.shard`)
    the lookup lowers to the sparse fast path of
    mxnet_tpu/shard/embedding.py: dedup -> owner-bucketed all-to-all
    index exchange -> local gather -> all-to-all vector return, with a
    `(unique_rows, D)` sparse backward and a scatter-add optimizer
    update on the owning shard only — no O(vocab) gradient, no
    host-side gather, table + state mesh-resident between steps.

    Integer index batches are REQUIRED (int32/int64 pass untouched); a
    float index batch raises instead of silently looking up the wrong
    row above 2**24. Outside a captured+sharded step the block behaves
    exactly like `Embedding` on integer inputs.
    """

    def __init__(self, input_dim, output_dim, dtype=np.float32,
                 weight_initializer=None, tiered=False, hbm_rows=None,
                 **kwargs):
        super().__init__(input_dim, output_dim, dtype=dtype,
                         weight_initializer=weight_initializer, **kwargs)
        # the capture-path marker mxnet_tpu/cachedop.py keys sparse
        # eligibility on (shard/embedding.py sparse_eligibility)
        self.weight._sharded_embedding = {"vocab": int(input_dim),
                                          "dim": int(output_dim)}
        if tiered:
            from ...base import MXNetError
            from ...shard import tiered as _tiered
            if hbm_rows is None or int(hbm_rows) < 1:
                raise MXNetError(
                    "ShardedEmbedding(tiered=True) needs hbm_rows >= 1 "
                    "(hot-cache rows per shard)")
            # conversion happens at Trainer.shard (shard/tiered.py
            # on_plan); registering the budget by NAME here lets
            # ShardPlan._check_large_replicated account HBM-resident
            # bytes before the table is ever converted
            self.weight._tiered = {"hbm_rows": int(hbm_rows)}
            _tiered.register_hbm_rows(self.weight.name, int(hbm_rows))

    def hybrid_forward(self, F, x, weight):
        from ...shard import embedding as _semb
        if is_symbolic(x):
            # a Symbol's dtype is only a HINT (usually None until bind);
            # enforce the integer contract when the hint is there — the
            # eager/captured paths below always enforce it at execution
            hint = getattr(x, "_dtype_hint", None)
            if hint is not None:
                _semb.check_index_dtype(hint)
            return F.Embedding(x, weight, input_dim=self._input_dim,
                               output_dim=self._output_dim)
        _semb.check_index_dtype(x.dtype)
        ctx = _semb.SparseLookupContext.active()
        if ctx is not None and ctx.handles(self.weight):
            # captured-step trace: recording is off, tracers flow raw
            return type(x)(_semb.lookup(self.weight, x._data,
                                        weight._data))
        ts = getattr(self.weight, "_tiered_state", None)
        if ts is not None:
            if getattr(self.weight, "_trace_override", None) is not None:
                # inside the capture machinery's ABSTRACT passes
                # (eval_shape pre-pass / jaxpr record, cachedop.py):
                # only shapes matter — the live record/consume passes
                # take the SparseLookupContext branch above — so the
                # plain gather below is shape-correct and never
                # materialises values
                return F.Embedding(x, weight, input_dim=self._input_dim,
                                   output_dim=self._output_dim)
            # eager/eval on a converted table: the live parameter is the
            # HOT CACHE, not the logical table — look up through the
            # host tier instead (slow path by design)
            import jax
            import jax.numpy as jnp
            try:
                # eager-only by construction (capture passes return
                # shapes above, foreign traces raise below); the host
                # sync IS the point of the read-through path
                # mxtpu: disable=E02
                idx = np.asarray(x._data)
            except (jax.errors.TracerArrayConversionError,
                    jax.errors.ConcretizationTypeError):
                from ...base import MXNetError
                raise MXNetError(
                    f"tiered embedding {self.weight.name!r} cannot be "
                    f"looked up inside a foreign trace — use the "
                    f"captured step (Trainer.capture) or call it "
                    f"eagerly") from None
            return type(x)(jnp.asarray(ts.lookup_np(idx)))
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)

    def __repr__(self):
        return (f"ShardedEmbedding({self._input_dim} -> "
                f"{self._output_dim})")


class ShardedMoE(HybridBlock):
    """Expert-parallel Mixture-of-Experts FFN (ISSUE 16;
    docs/PERFORMANCE.md "Expert parallelism").

    Replaces one dense FFN with ``num_experts`` expert FFNs and a
    learned top-``k`` softmax router. The stacked expert banks
    (``expert_ffn*_weight`` / ``_bias``, dim 0 = expert index) are
    routed to the 'tp' mesh axis by `shard.DEFAULT_RULES`' axis
    override, so each device holds ``E / tp`` experts; under a captured
    step with a shard plan the dispatch/combine lowers to the
    shard/moe.py 2-all-to-all exchange (tokens sharded over (dp, tp)
    jointly — the GShard layout). Without a plan, on an axis of size 1,
    or with non-divisible expert/token counts, the layer degenerates to
    pure local dispatch with zero collectives.

    Capacity-factor token dropping is LOUD, never silent: the
    ``dropped`` aux parameter accumulates the psum'd drop count,
    ``overflow_frac`` holds the last step's dropped fraction of
    (token, choice) pairs, and `publish_metrics()` forwards both to the
    observability registry (`moe_tokens_dropped` counter,
    `moe_overflow_frac` / `moe_aux_loss` gauges). A dropped token's MoE
    output is exactly zero, so with ``residual=True`` (default) it
    passes through the skip connection unchanged — gradients included.

    The Switch-style load-balancing auxiliary loss
    ``E * sum_e f_e * P_e`` (scaled by ``aux_loss_coef``) is threaded
    into the captured/imperative training loss automatically by
    `Trainer.capture`; in a hand-written eager loop read it from
    ``self.last_aux_loss`` after the forward and add it yourself.
    """

    def __init__(self, units, hidden_units, num_experts, k=2,
                 capacity_factor=1.25, aux_loss_coef=0.01,
                 activation="relu", residual=True, normalize_gates=True,
                 dtype=np.float32, weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        if not 1 <= int(k) <= int(num_experts):
            raise MXNetError(f"ShardedMoE: k={k} must be in "
                             f"[1, num_experts={num_experts}]")
        if capacity_factor <= 0:
            raise MXNetError("ShardedMoE: capacity_factor must be > 0")
        from ...shard import moe as _smoe
        if activation not in _smoe._ACTS:
            raise MXNetError(f"ShardedMoE: unknown activation "
                             f"{activation!r} (have "
                             f"{sorted(_smoe._ACTS)})")
        self._units = int(units)
        self._hidden = int(hidden_units)
        self._num_experts = int(num_experts)
        self._k = int(k)
        self._capacity_factor = float(capacity_factor)
        self._aux_loss_coef = float(aux_loss_coef)
        self._activation = activation
        self._residual = bool(residual)
        self._normalize_gates = bool(normalize_gates)
        self.last_aux_loss = None
        self._published_dropped = 0.0
        E, d, h = self._num_experts, self._units, self._hidden
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(E, d), dtype=dtype,
                init=weight_initializer)
            self.expert_ffn1_weight = self.params.get(
                "expert_ffn1_weight", shape=(E, d, h), dtype=dtype,
                init=weight_initializer)
            self.expert_ffn1_bias = self.params.get(
                "expert_ffn1_bias", shape=(E, h), dtype=dtype,
                init="zeros")
            self.expert_ffn2_weight = self.params.get(
                "expert_ffn2_weight", shape=(E, h, d), dtype=dtype,
                init=weight_initializer)
            self.expert_ffn2_bias = self.params.get(
                "expert_ffn2_bias", shape=(E, d), dtype=dtype,
                init="zeros")
            # loud-accounting aux state (BN running-stat pattern):
            # last-step aux loss + overflow fraction, cumulative drops
            self.aux_loss = self.params.get(
                "aux_loss", shape=(1,), init="zeros", grad_req="null")
            self.overflow_frac = self.params.get(
                "overflow_frac", shape=(1,), init="zeros",
                grad_req="null")
            self.dropped = self.params.get(
                "dropped", shape=(1,), init="zeros", grad_req="null")

    def _routing(self, n_tokens):
        """(mesh, axis, layout) for this layer under the enclosing
        captured step's plan — honouring per-param axis overrides via
        `plan.spec_for` on the expert bank (None/size-1/non-divisible
        all land on the local path)."""
        from ...shard import moe as _smoe
        plan = _smoe.current_plan()
        mesh = axis = data_axis = None
        if plan is not None:
            E, d, h = self._num_experts, self._units, self._hidden
            spec = tuple(plan.spec_for(self.expert_ffn1_weight.name,
                                       (E, d, h)))
            if spec and isinstance(spec[0], str):
                mesh, axis = plan.mesh, spec[0]
                data_axis = plan.data_axis
        lay = _smoe.routing_layout(
            n_tokens, self._num_experts, self._k, self._capacity_factor,
            mesh=mesh, axis=axis, data_axis=data_axis)
        return mesh, axis, data_axis, lay

    def hybrid_forward(self, F, x, gate_weight, expert_ffn1_weight,
                       expert_ffn1_bias, expert_ffn2_weight,
                       expert_ffn2_bias, aux_loss=None,
                       overflow_frac=None, dropped=None):
        from ...shard import moe as _smoe
        from ..block import _TraceContext
        if is_symbolic(x):
            raise MXNetError(
                "ShardedMoE has no symbolic/export path — data-dependent "
                "token routing does not lower to a static symbol graph; "
                "hybridize/capture the imperative block instead")
        if x.shape[-1] != self._units:
            raise MXNetError(
                f"ShardedMoE: input feature dim {x.shape[-1]} != "
                f"units {self._units}")
        n_tokens = 1
        for s in x.shape[:-1]:
            n_tokens *= int(s)
        mesh, axis, data_axis, lay = self._routing(n_tokens)
        itemsize = np.dtype(x.dtype).itemsize
        _smoe.report_site({
            "name": self.name, "sharded": lay["sharded"],
            "reason": lay["reason"], "capacity": lay["capacity"],
            "n_exp_shards": lay["n_exp_shards"],
            "a2a_per_pass": _smoe.A2A_PER_LAYER if lay["sharded"] else 0,
            "bytes": _smoe.a2a_bytes_per_step(
                lay, self._num_experts, self._units, itemsize)})

        def fn(xv, gw, w1, b1, w2, b2, _E=self._num_experts,
               _k=self._k, _cf=self._capacity_factor,
               _act=self._activation, _nrm=self._normalize_gates,
               _mesh=mesh, _axis=axis, _dax=data_axis):
            shp = xv.shape
            y2, aux, frac, drops = _smoe.moe_forward(
                xv.reshape((-1, shp[-1])), gw, w1, b1, w2, b2,
                n_experts=_E, k=_k, capacity_factor=_cf,
                activation=_act, normalize_gates=_nrm,
                mesh=_mesh, axis=_axis, data_axis=_dax)
            return y2.reshape(shp), aux, frac, drops

        y, aux, frac, drops = _apply(
            fn, [x, gate_weight, expert_ffn1_weight, expert_ffn1_bias,
                 expert_ffn2_weight, expert_ffn2_bias], n_out=4)
        out = (y + x) if self._residual else y

        if autograd.is_training():
            _report_aux_update(self.aux_loss, aux.reshape((1,)))
            _report_aux_update(self.overflow_frac, frac.reshape((1,)))
            _report_aux_update(self.dropped,
                               dropped + drops.reshape((1,)))
        scaled = aux * self._aux_loss_coef
        if not _smoe.report_aux_loss(scaled) \
                and _TraceContext.active() is None:
            # hand-written eager loop: the caller owns the aux loss
            # (never stash a tracer on the block under a trace)
            self.last_aux_loss = scaled
        return out

    def publish_metrics(self):
        """Flush the layer's drop/aux accounting to the observability
        registry: the cumulative `moe_tokens_dropped{layer=}` counter
        delta since the last publish plus the `moe_overflow_frac` /
        `moe_aux_loss` gauges. Host-syncs the three scalars — call it
        between steps (eval boundaries, bench teardown), never inside
        a captured loss. Returns {"dropped", "overflow_frac",
        "aux_loss"} as floats."""
        from ...observability import registry
        dropped = float(self.dropped.data().asnumpy()[0])
        frac = float(self.overflow_frac.data().asnumpy()[0])
        aux = float(self.aux_loss.data().asnumpy()[0])
        reg = registry()
        delta = dropped - self._published_dropped
        if delta > 0:
            reg.counter("moe_tokens_dropped", layer=self.name).inc(delta)
            self._published_dropped = dropped
        reg.gauge("moe_overflow_frac", layer=self.name).set(frac)
        reg.gauge("moe_aux_loss", layer=self.name).set(aux)
        return {"dropped": dropped, "overflow_frac": frac,
                "aux_loss": aux}

    def __repr__(self):
        return (f"ShardedMoE({self._units} -> {self._hidden} -> "
                f"{self._units}, experts={self._num_experts}, "
                f"k={self._k}, cf={self._capacity_factor})")


class BatchNorm(HybridBlock):
    """Batch normalisation with functional running-stat updates."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, in_channels=0,
                 beta_initializer="zeros", gamma_initializer="ones", **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")
            self.running_mean = self.params.get(
                "running_mean", shape=(in_channels,), init="zeros",
                allow_deferred_init=True, grad_req="null")
            self.running_var = self.params.get(
                "running_var", shape=(in_channels,), init="ones",
                allow_deferred_init=True, grad_req="null")

    def _infer_shapes(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p._finish_deferred_init((c,))

    def cast(self, dtype):
        if np.dtype(dtype) == np.float16:
            dtype = np.float32
        return super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        if is_symbolic(x):
            # symbolic trace (export path): aux-state updates are handled
            # by the Executor's train registry, not the gluon tape
            return F.BatchNorm(x, gamma, beta, running_mean, running_var,
                               eps=self._epsilon, momentum=self._momentum,
                               axis=self._axis, fix_gamma=not self._scale,
                               use_global_stats=self._use_global_stats)
        training = autograd.is_training() and not self._use_global_stats
        outs = _apply(
            lambda a, g, b, mm, mv, _e=self._epsilon, _m=self._momentum,
            _t=training, _ax=self._axis:
            K.batch_norm(a, g, b, mm, mv, _e, _m, _t, _ax),
            [x, gamma, beta, running_mean, running_var], n_out=3)
        out, new_mean, new_var = outs
        if training:
            _report_aux_update(self.running_mean, new_mean)
            _report_aux_update(self.running_var, new_var)
        return out

    def __repr__(self):
        return (f"BatchNorm(axis={self._axis}, eps={self._epsilon}, "
                f"momentum={self._momentum}, in_channels={self.in_channels})")



class BatchNormReLU(BatchNorm):
    """BatchNorm with a fused ReLU epilogue (reference: nn.BatchNormReLU
    — upstream fuses via cuDNN; XLA fuses the relu into the BN kernel
    here, so subclass + relu is already the fused program)."""

    def hybrid_forward(self, F, x, *args, **kwargs):
        out = super().hybrid_forward(F, x, *args, **kwargs)
        return F.relu(out)   # F-dispatch keeps the symbolic path alive


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")

    def _infer_shapes(self, x):
        c = x.shape[self._axis]
        self.gamma._finish_deferred_init((c,))
        self.beta._finish_deferred_init((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        if not is_symbolic(x) and self._axis in (-1, x.ndim - 1):
            # fused fast path (Pallas on TPU)
            from ...ops.pallas_kernels import fused_layer_norm

            def fn(a, g, b, _e=self._epsilon):
                return fused_layer_norm(a, g, b, eps=_e)
            return _apply(fn, [x, gamma, beta])
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)

    def __repr__(self):
        return f"LayerNorm(axis={self._axis}, eps={self._epsilon})"


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")

    def _infer_shapes(self, x):
        c = x.shape[1]
        self.gamma._finish_deferred_init((c,))
        self.beta._finish_deferred_init((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")

    def _infer_shapes(self, x):
        c = x.shape[1]
        self.gamma._finish_deferred_init((c,))
        self.beta._finish_deferred_init((c,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act_type = activation

    def _alias(self):
        return getattr(self, "_act_type", "activation")

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, in_channels=1, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer as init_mod
        with self.name_scope():
            self.alpha = self.params.get(
                "alpha", shape=(in_channels,),
                init=alpha_initializer or init_mod.Constant(0.25))

    def hybrid_forward(self, F, x, alpha):
        return F.PReLU(x, alpha)


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        if self._beta == 1.0:
            return F.silu(x)
        return x * F.sigmoid(x * self._beta)


SiLU = Swish


class GELU(HybridBlock):
    def __init__(self, approximation="erf", **kwargs):
        super().__init__(**kwargs)
        self._approx = approximation != "erf"

    def hybrid_forward(self, F, x):
        import jax
        return _apply(lambda a, _t=self._approx: jax.nn.gelu(a, approximate=_t),
                      [x])


class Concurrent(Sequential):
    """Parallel branches concatenated along an axis (reference: contrib)."""

    def __init__(self, axis=-1, **kwargs):
        super().__init__(**kwargs)
        self.axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        from ..block import is_symbolic
        if is_symbolic(outs[0]):
            from ...symbol import ops as S
            return S.concat(*outs, dim=self.axis)
        from ...ops.tensor_ops import concat
        return concat(*outs, dim=self.axis)
