"""Optimizer base (reference: python/paddle/optimizer/optimizer.py:91).

TPU-native design: parameter updates are pure-jax expressions applied through
the trace-aware ``_set_data`` path, so ``opt.step()`` inside a ``to_static``
train step compiles into the same XLA program as forward+backward (the
reference reaches the same shape via fused adamw ops in ProgramDesc).
Accumulator state lives in Tensors keyed by parameter name, mirroring the
reference's accumulator scope vars.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.autograd import no_grad
from ..core import dtype as dtype_mod
from .lr import LRScheduler


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._learning_rate = learning_rate
        # reference optimizer.py:91 accepts a flat Tensor list OR a list
        # of group dicts ({'params': [...], 'learning_rate': factor,
        # 'weight_decay'/'beta1'/...: per-group overrides}); group
        # 'learning_rate' multiplies the global lr, like
        # optimize_attr['learning_rate'] (_create_param_lr :566)
        self._param_groups = None
        self._param_overrides: Dict[int, dict] = {}
        if parameters is not None:
            plist = list(parameters)
            if plist and isinstance(plist[0], dict):
                flat: list = []
                self._param_groups = []
                seen = set()
                for group in plist:
                    g = dict(group)
                    if "params" not in g:
                        raise ValueError(
                            "each optimizer parameter group dict needs a "
                            f"'params' key; got keys {sorted(g)}")
                    ps = g.get("params")
                    ps = [ps] if isinstance(ps, Tensor) else list(ps)
                    g["params"] = ps
                    ov = {k: v for k, v in g.items() if k != "params"}
                    for p in ps:
                        if id(p) in seen:
                            raise ValueError(
                                "some parameters appear in more than one "
                                "optimizer parameter group")
                        seen.add(id(p))
                        if ov:
                            self._param_overrides[id(p)] = ov
                        flat.append(p)
                    self._param_groups.append(g)
                self._parameter_list = flat
            else:
                self._parameter_list = plist
        else:
            self._parameter_list = None
        self._lr_factor = 1.0
        self._grad_clip = grad_clip
        self._name = name
        self._regularizer = None
        if isinstance(weight_decay, float) or isinstance(weight_decay, int):
            self._weight_decay = float(weight_decay)
        elif weight_decay is None:
            self._weight_decay = None
        else:  # paddle.regularizer.L1Decay/L2Decay (or coeff-duck-typed)
            self._weight_decay = float(getattr(weight_decay, "_coeff", getattr(
                weight_decay, "coeff", 0.0)))
            if hasattr(weight_decay, "_grad_term"):
                self._regularizer = weight_decay
        # name → {acc_name: Tensor}
        self._accumulators: Dict[str, Dict[str, Tensor]] = {}
        self._acc_inits: Dict[tuple, object] = {}  # float init or callable thunk
        self._global_step = 0

    # -- lr ----------------------------------------------------------------

    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "set_lr is not allowed when learning rate is an LRScheduler; "
                "use scheduler.step() instead")
        self._learning_rate = float(value)
        if self._lr_t is not None:
            self._lr_t._set_data(jnp.asarray(float(value), dtype=jnp.float32))

    _lr_t = None

    def _lr_array(self):
        """Learning rate as a jax scalar.  Under a to_static trace the value
        is read through a persistent Tensor so it becomes a *program input* —
        scheduler steps and set_lr between compiled calls do not recompile
        (the reference feeds lr as a scope variable for the same reason)."""
        from ..core import tensor as tensor_mod

        if isinstance(self._learning_rate, LRScheduler):
            lr = self._learning_rate._lr_tensor()._value()
        elif tensor_mod._trace_hook is not None:
            if self._lr_t is None:
                self._lr_t = tensor_mod.external_tensor(
                    np.float32(self.get_lr()))
            lr = self._lr_t._value()
        else:
            lr = jnp.asarray(self.get_lr(), dtype=jnp.float32)
        if self._lr_factor != 1.0:
            # per-group factor (reference optimize_attr['learning_rate'],
            # applied as global_lr * param_lr in _create_param_lr :580)
            lr = lr * jnp.float32(self._lr_factor)
        return lr

    # -- accumulators -------------------------------------------------------

    def _param_key(self, p: Tensor) -> str:
        return p.name or f"param_{id(p)}"

    def _get_accumulator(self, name: str, p: Tensor, init=0.0,
                         dtype=None, shape=None, init_from=None) -> Tensor:
        key = self._param_key(p)
        accs = self._accumulators.setdefault(key, {})
        if name not in accs:
            from ..core import tensor as tensor_mod

            dt = dtype or p._value().dtype
            shape = tuple(p.shape) if shape is None else tuple(shape)
            # external_tensor: accumulators lazily created inside a traced
            # train step must still be persistent program state
            if init_from is not None:
                accs[name] = tensor_mod.external_tensor(init_from)
            else:
                accs[name] = tensor_mod.external_tensor(
                    lambda: jnp.full(shape, init, dtype=dt))
            # init value kept for skip-step rollback (amp GradScaler);
            # derived accumulators (master weights) store their thunk so
            # rollback re-derives from the rolled-back param
            self._acc_inits[(key, name)] = (
                init_from if init_from is not None else init)
        return accs[name]

    # -- main entry points ---------------------------------------------------

    def _collect_params_grads(self):
        params = self._parameter_list
        if params is None:
            raise ValueError("optimizer constructed without parameters")
        out = []
        for p in params:
            if not getattr(p, "trainable", True):
                continue
            g = p.grad
            if g is None:
                continue
            out.append((p, g))
        return out

    # attr <-> group-dict key pairs a group may override (reference
    # _update_param_group in each optimizer subclass).  weight decay
    # lives under different attrs per family: coupled `_weight_decay`
    # (SGD/Momentum regularizer fold), decoupled `_wd` (AdamW/Lamb),
    # `_lars_weight_decay` (Lars) — swap every one that exists.
    _GROUP_OVERRIDE_ATTRS = (
        ("_weight_decay", "weight_decay"), ("_wd", "weight_decay"),
        ("_lars_weight_decay", "weight_decay"),
        ("_beta1", "beta1"), ("_beta2", "beta2"),
        ("_epsilon", "epsilon"), ("_momentum", "momentum"))

    def _update_with_overrides(self, p, garr):
        ov = self._param_overrides.get(id(p))
        if not ov:
            self._update_param(p, garr)
            return
        saved = {}
        for attr, key in self._GROUP_OVERRIDE_ATTRS:
            if key in ov and hasattr(self, attr):
                saved[attr] = getattr(self, attr)
                setattr(self, attr, ov[key])
        if "learning_rate" in ov:
            self._lr_factor = float(ov["learning_rate"])
        try:
            self._update_param(p, garr)
        finally:
            for attr, val in saved.items():
                setattr(self, attr, val)
            self._lr_factor = 1.0

    @no_grad()
    def step(self):
        # one named scope over the whole update (``optimizer.adamw`` for
        # AdamW): in a compiled train step every op of the update carries
        # it in its op_name, which is how a profiler trace tells the
        # optimizer's device time from the backward's
        with jax.named_scope(f"optimizer.{type(self).__name__.lower()}"):
            params_grads = self._collect_params_grads()
            if self._grad_clip is not None:
                params_grads = self._grad_clip(params_grads)
            self._global_step += 1
            for p, g in params_grads:
                garr = g._value() if isinstance(g, Tensor) else g
                if garr.dtype in (jnp.bfloat16, jnp.float16):
                    garr = garr.astype(jnp.float32)
                self._update_with_overrides(p, garr)

    minimize_step = step

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..core import dispatch

        if dispatch._static_record_hook is not None:
            # static-graph idiom: minimize marks the recording program as
            # a TRAIN program (reference: the ProgramDesc carries the
            # backward + sgd ops after minimize, so exe.run applies
            # updates every call).  Never run an eager step here — the
            # placeholders hold dummy values.
            from ..nn.layer_base import Parameter
            from ..static import program as prog_mod

            prog = prog_mod.default_main_program()
            if parameters is not None:
                self._parameter_list = list(parameters)
            if self._parameter_list is None:
                seen, params = set(), []
                for op in prog._raw:
                    for a in op.inputs:
                        if (isinstance(a, Parameter)
                                and not a.stop_gradient
                                and getattr(a, "trainable", True)
                                and id(a) not in seen):
                            seen.add(id(a))
                            params.append(a)
                if not params:
                    raise ValueError(
                        "minimize() found no trainable Parameters in the "
                        "recording program (was it already run/finalized, "
                        "or built without static.nn/create_parameter "
                        "layers?); pass parameters= explicitly")
                self._parameter_list = params
            prog._train_spec = (loss, self)
            prog._train_cache.clear()     # a re-minimize replaces the spec
            return None, None
        if parameters is not None and self._parameter_list is None:
            self._parameter_list = list(parameters)
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=False):
        if self._parameter_list:
            for p in self._parameter_list:
                p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def _apply(self, p: Tensor, new_value):
        p._set_data(new_value.astype(p._value().dtype))

    # -- master weights (AMP-O2 / reference multi_precision) ---------------
    # When a parameter is stored in a low dtype (bf16/f16 after
    # amp.decorate), the optimizer keeps an f32 master copy in its
    # accumulators: updates accumulate in f32 and the param gets the
    # cast-down view, so lr*grad increments far below bf16 resolution are
    # not lost (reference: optimizer.py _multi_precision master weights).
    def _is_low_precision(self, p: Tensor):
        return p._data.dtype in (jnp.bfloat16, jnp.float16)

    def _master_tensor(self, p: Tensor) -> Tensor:
        # init thunk reads p._data (the raw payload), which stays the
        # concrete pre-step array even while a to_static trace is
        # active (trace reads go through env, not the attribute)
        return self._get_accumulator(
            "master_weight", p, dtype=jnp.float32,
            init_from=lambda: p._data.astype(jnp.float32))

    def _master_value(self, p: Tensor):
        if self._is_low_precision(p):
            return self._master_tensor(p)._value().astype(jnp.float32)
        return p._value().astype(jnp.float32)

    def _apply_master(self, p: Tensor, new32):
        if self._is_low_precision(p):
            self._master_tensor(p)._set_data(new32)
        self._apply(p, new32)

    def _update_param(self, p: Tensor, g):
        raise NotImplementedError

    def _decayed_grad(self, p, g):
        """Regularization folded into the gradient (reference: coupled
        weight decay for SGD/Momentum family). L1/L2 shape comes from the
        paddle.regularizer object when one was passed."""
        if self._regularizer is not None:
            g = g + self._regularizer._grad_term(
                p._value()).astype(g.dtype)
        elif self._weight_decay:
            g = g + self._weight_decay * p._value().astype(g.dtype)
        return g

    # -- state dict ----------------------------------------------------------

    def state_dict(self):
        sd = {}
        for pkey, accs in self._accumulators.items():
            for aname, t in accs.items():
                sd[f"{pkey}/{aname}"] = t
        sd["@global_step"] = self._global_step
        if isinstance(self._learning_rate, LRScheduler):
            sd["@lr_scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        import numpy as np

        # Saved accumulator keys carry the SAVING run's parameter names.
        # Auto-generated names (linear_0.weight, …) restart per process, so
        # a model built later in the same process gets different names; map
        # saved param keys onto the current parameter list by position (the
        # accumulator dict iterates in parameter order on both sides).
        saved_pkeys = []
        for k in state_dict:
            if k.startswith("@"):
                continue
            pk = k.rsplit("/", 1)[0]
            if pk not in saved_pkeys:
                saved_pkeys.append(pk)
        params = list(self._parameter_list or [])
        cur_names = [self._param_key(p) for p in params]
        remap = {}
        if saved_pkeys and set(saved_pkeys) != set(cur_names) \
                and len(saved_pkeys) == len(cur_names):
            remap = dict(zip(saved_pkeys, cur_names))
            # validate the positional pairing: every non-scalar saved
            # accumulator must match its mapped parameter's shape — else
            # this is a different model, not a renamed one
            shapes = {self._param_key(p): tuple(p.shape) for p in params}
            for k, v in state_dict.items():
                if k.startswith("@"):
                    continue
                pk = remap[k.rsplit("/", 1)[0]]
                vs = tuple(getattr(v, "shape", ()) or ())
                if vs and vs != shapes[pk]:
                    raise ValueError(
                        f"optimizer state {k!r} (shape {vs}) does not fit "
                        f"parameter {pk!r} (shape {shapes[pk]}); the saved "
                        f"state appears to be for a different model")

        for k, v in state_dict.items():
            if k == "@global_step":
                self._global_step = int(v)
                continue
            if k == "@lr_scheduler":
                if isinstance(self._learning_rate, LRScheduler):
                    self._learning_rate.set_state_dict(v)
                continue
            pkey, aname = k.rsplit("/", 1)
            pkey = remap.get(pkey, pkey)
            arr = v._value() if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            accs = self._accumulators.setdefault(pkey, {})
            existing = accs.get(aname)
            if existing is not None \
                    and tuple(existing.shape) == tuple(arr.shape) \
                    and existing._value().dtype == arr.dtype:
                # restore IN PLACE: a compiled train step lifted the
                # existing accumulator tensor as persistent program
                # state, so a mid-run restore (divergence-sentry
                # rollback) must write through the same object —
                # replacing it would leave the program updating a
                # tensor the optimizer no longer reads
                existing._set_data(arr)
            else:
                accs[aname] = Tensor._wrap(arr)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        lr = self._lr_array()
        self._apply_master(p, self._master_value(p)
                           - lr * g.astype(jnp.float32))


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        # all update math in f32: an f16/bf16 lr or velocity would flush
        # warmup-scale values (< f16 subnormal floor) to zero
        lr = self._lr_array()
        g32 = g.astype(jnp.float32)
        vel = self._get_accumulator("velocity", p, dtype=jnp.float32)
        v_new = self._momentum * vel._value() + g32
        vel._set_data(v_new)
        if self._use_nesterov:
            upd = g32 + self._momentum * v_new
        else:
            upd = v_new
        self._apply_master(p, self._master_value(p) - lr * upd)


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, *, moment_dtype=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._multi_precision = multi_precision
        # TPU extension (not in the reference API): store moment1/moment2 in
        # a narrower dtype ("bfloat16") to halve optimizer HBM traffic —
        # ~8 B/param/step saved; at 345M params that is ~2.8 GB/step off the
        # AdamW update's ~9.7 GB.  The update math itself stays f32 (moments
        # are widened on read, rounded on store).  bf16's 8 mantissa bits
        # add ~0.4% relative noise to the moments; default stays f32.
        self._moment_dtype = (None if moment_dtype is None
                              else jnp.dtype(moment_dtype))

    def _adam_update(self, p, g, decoupled_wd=0.0):
        lr = self._lr_array()
        mdt = self._moment_dtype or jnp.float32
        m = self._get_accumulator("moment1", p, dtype=mdt)
        v = self._get_accumulator("moment2", p, dtype=mdt)
        b1p = self._get_accumulator("beta1_pow", p, init=1.0, dtype=jnp.float32, shape=())
        b2p = self._get_accumulator("beta2_pow", p, init=1.0, dtype=jnp.float32, shape=())
        g32 = g.astype(jnp.float32)
        m_new = self._beta1 * m._value().astype(jnp.float32) \
            + (1 - self._beta1) * g32
        v_new = self._beta2 * v._value().astype(jnp.float32) \
            + (1 - self._beta2) * jnp.square(g32)
        b1p_new = b1p._value() * self._beta1
        b2p_new = b2p._value() * self._beta2
        m._set_data(m_new.astype(mdt))
        v._set_data(v_new.astype(mdt))
        b1p._set_data(b1p_new)
        b2p._set_data(b2p_new)
        m_hat = m_new / (1.0 - b1p_new)
        v_hat = v_new / (1.0 - b2p_new)
        p32 = self._master_value(p)
        if decoupled_wd:
            p32 = p32 * (1.0 - lr * decoupled_wd)
        new32 = p32 - lr * m_hat / (jnp.sqrt(v_hat) + self._epsilon)
        self._apply_master(p, new32)

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        self._adam_update(p, g)


class AdamW(Adam):
    """Decoupled weight decay (reference: optimizer/adamw.py → fused adamw op)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 *, moment_dtype=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, name,
                         moment_dtype=moment_dtype)
        self._wd = float(weight_decay) if not hasattr(weight_decay, "_coeff") \
            else float(weight_decay._coeff)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _update_param(self, p, g):
        wd = self._wd
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            wd = 0.0
        self._adam_update(p, g, decoupled_wd=wd)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        lr = self._lr_array()
        m = self._get_accumulator("moment", p, dtype=jnp.float32)
        u = self._get_accumulator("inf_norm", p, dtype=jnp.float32)
        b1p = self._get_accumulator("beta1_pow", p, init=1.0, dtype=jnp.float32, shape=())
        g32 = g.astype(jnp.float32)
        m_new = self._beta1 * m._value() + (1 - self._beta1) * g32
        u_new = jnp.maximum(self._beta2 * u._value(), jnp.abs(g32))
        b1p_new = b1p._value() * self._beta1
        m._set_data(m_new); u._set_data(u_new); b1p._set_data(b1p_new)
        self._apply_master(p, self._master_value(p)
                           - lr / (1 - b1p_new) * m_new
                           / (u_new + self._epsilon))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        lr = self._lr_array()
        acc = self._get_accumulator("moment", p, init=self._init_acc,
                                    dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        acc_new = acc._value() + jnp.square(g32)
        acc._set_data(acc_new)
        self._apply_master(p, self._master_value(p)
                           - lr * g32
                           / (jnp.sqrt(acc_new) + self._epsilon))


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        lr = self._lr_array()
        ms = self._get_accumulator("mean_square", p, dtype=jnp.float32)
        mom = self._get_accumulator("momentum", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        ms_new = self._rho * ms._value() + (1 - self._rho) * jnp.square(g32)
        ms._set_data(ms_new)
        denom = ms_new
        if self._centered:
            mg = self._get_accumulator("mean_grad", p, dtype=jnp.float32)
            mg_new = self._rho * mg._value() + (1 - self._rho) * g32
            mg._set_data(mg_new)
            denom = ms_new - jnp.square(mg_new)
        upd = self._momentum * mom._value() + lr * g32 / jnp.sqrt(denom + self._epsilon)
        mom._set_data(upd)
        self._apply_master(p, self._master_value(p) - upd)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._epsilon, self._rho = epsilon, rho

    def _update_param(self, p, g):
        g = self._decayed_grad(p, g)
        lr = self._lr_array()
        avg_sq_g = self._get_accumulator("avg_squared_grad", p, dtype=jnp.float32)
        avg_sq_u = self._get_accumulator("avg_squared_update", p, dtype=jnp.float32)
        g32 = g.astype(jnp.float32)
        asg = self._rho * avg_sq_g._value() + (1 - self._rho) * jnp.square(g32)
        upd = -jnp.sqrt((avg_sq_u._value() + self._epsilon) /
                        (asg + self._epsilon)) * g32
        asu = self._rho * avg_sq_u._value() + (1 - self._rho) * jnp.square(upd)
        avg_sq_g._set_data(asg)
        avg_sq_u._set_data(asu)
        self._apply_master(p, self._master_value(p) + lr * upd)


class Lars(Optimizer):
    """LARS momentum (reference: fluid/optimizer.py:1969
    LarsMomentumOptimizer; kernel lars_momentum_op.h):

        local_lr = lr * lars_coeff * ||p|| / (eps + ||g|| + wd * ||p||)
        velocity = mu * velocity + local_lr * (g + wd * p)
        p       -= velocity

    Layers whose name matches ``exclude_from_weight_decay`` skip the decay
    term (both in local_lr and the velocity update), like the reference's
    name-substring match.
    """

    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, epsilon=0,
                 multi_precision=False, rescale_grad=1.0, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._momentum = float(momentum)
        self._lars_coeff = float(lars_coeff)
        self._lars_weight_decay = float(lars_weight_decay)
        self._epsilon = float(epsilon)
        self._exclude = list(exclude_from_weight_decay or [])
        self._rescale_grad = float(rescale_grad)

    def _update_param(self, p, g):
        lr = self._lr_array()
        g32 = g.astype(jnp.float32) * self._rescale_grad
        p32 = self._master_value(p)
        wd = self._lars_weight_decay
        pname = p.name or ""
        if any(tok in pname for tok in self._exclude):
            wd = 0.0
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g32)))
        # reference kernel guard: fall back to plain lr when either norm
        # is zero (fresh zero-init params would otherwise stall at 0)
        local_lr = jnp.where(
            (p_norm > 0) & (g_norm > 0),
            lr * self._lars_coeff * p_norm
            / (self._epsilon + g_norm + wd * p_norm),
            lr)
        vel = self._get_accumulator("velocity", p, dtype=jnp.float32)
        v_new = self._momentum * vel._value() + local_lr * (g32 + wd * p32)
        vel._set_data(v_new)
        self._apply_master(p, p32 - v_new)


# reference class name (fluid/optimizer.py:1969)
LarsMomentumOptimizer = Lars


class Lamb(Optimizer):
    """Layer-wise adaptive moments (reference: optimizer/lamb.py; the
    distributed_fused_lamb op family collapses to this math under jit)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._wd = lamb_weight_decay
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _update_param(self, p, g):
        lr = self._lr_array()
        m = self._get_accumulator("moment1", p, dtype=jnp.float32)
        v = self._get_accumulator("moment2", p, dtype=jnp.float32)
        b1p = self._get_accumulator("beta1_pow", p, init=1.0, dtype=jnp.float32, shape=())
        b2p = self._get_accumulator("beta2_pow", p, init=1.0, dtype=jnp.float32, shape=())
        g32 = g.astype(jnp.float32)
        m_new = self._beta1 * m._value() + (1 - self._beta1) * g32
        v_new = self._beta2 * v._value() + (1 - self._beta2) * jnp.square(g32)
        b1p_new = b1p._value() * self._beta1
        b2p_new = b2p._value() * self._beta2
        m._set_data(m_new); v._set_data(v_new)
        b1p._set_data(b1p_new); b2p._set_data(b2p_new)
        m_hat = m_new / (1 - b1p_new)
        v_hat = v_new / (1 - b2p_new)
        p32 = self._master_value(p)
        wd = self._wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        r = m_hat / (jnp.sqrt(v_hat) + self._epsilon) + wd * p32
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        self._apply_master(p, p32 - lr * trust * r)
