"""The Layer container system.

TPU-native analog of the reference's ``paddle.nn.Layer``
(reference: python/paddle/fluid/dygraph/layers.py): named parameter /
sublayer / buffer registries with attribute magic, state_dict round-trip,
train/eval flags, and forward hooks.

Two execution paths share these Layers:
- eager: ``layer(x)`` runs ops through the autograd tape
- jit: ``paddle_tpu.jit`` binds the parameter pytree to traced arrays and
  differentiates the whole step with ``jax.grad`` (SURVEY §7 design stance).
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import convert_dtype, get_default_dtype
from ..core.tensor import Parameter, Tensor
from ..observability.compiles import setup_span
from ..utils import monitor
from . import initializer as I


class ParamAttr:
    """reference: python/paddle/fluid/param_attr.py."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        if attr is False:
            return False
        raise TypeError(f"Cannot interpret {attr!r} as ParamAttr")


_unique_counters: Dict[str, int] = {}


def unique_name(prefix: str) -> str:
    """paddle.utils.unique_name-style 'prefix_N' generator (reference:
    python/paddle/fluid/unique_name.py)."""
    i = _unique_counters.get(prefix, 0)
    _unique_counters[prefix] = i + 1
    return f"{prefix}_{i}"


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = convert_dtype(dtype)
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._auto_name = None  # lazy 'linear_0'-style unique scope
        self._param_suffix_counts = {}

    # -- attribute magic ---------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            for d in (layers, buffers):
                d.pop(name, None) if d else None
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning layers")
            params.pop(name, None) if params else None
            layers[name] = value
            value._set_scope(name)
        elif params is not None and name in params:
            if value is None:
                del params[name]
            else:
                raise TypeError(
                    f"cannot assign non-Parameter to parameter {name!r}")
        elif buffers is not None and name in buffers:
            buffers[name] = value if isinstance(value, Tensor) else Tensor(value)
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = (list(self._parameters) + list(self._sub_layers)
                 + list(self._buffers))
        return sorted(set(super().__dir__() + extra))

    # -- construction helpers ---------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Optional[Parameter]:
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = convert_dtype(dtype) or self._dtype or get_default_dtype()
        init = attr.initializer or default_initializer or (
            I.Constant(0.0) if is_bias else I.XavierNormal())
        name = attr.name
        if name is None:
            # paddle-convention auto-name 'linear_0.w_0' / 'linear_0.b_0'
            # so apply_decay_param_fun-style predicates work unmodified
            if self._auto_name is None:
                self._auto_name = unique_name(self._name_scope)
            suffix = "b" if is_bias else "w"
            k = self._param_suffix_counts.get(suffix, 0)
            self._param_suffix_counts[suffix] = k + 1
            name = f"{self._auto_name}.{suffix}_{k}"
        t0 = time.perf_counter()
        with setup_span("setup.param_init"):
            p = Parameter(init(tuple(shape), dtype), name=name,
                          trainable=attr.trainable,
                          regularizer=attr.regularizer,
                          need_clip=attr.need_clip)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        # always-on set-up counters (the eager initialiser is the cost)
        monitor.stat_add("setup.param_init_s", time.perf_counter() - t0)
        monitor.stat_add("setup.param_init_count")
        return p

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        if parameter is None:
            self._parameters[name] = None
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        if sublayer is not None:
            sublayer._set_scope(name)
        return sublayer

    def _set_scope(self, attr: str):
        """Called by the layer that holds this one as ``attr``: from now
        on ``__call__`` runs ``forward`` under
        ``jax.named_scope("<attr>:<ClassName>")`` (the name
        ``named_parameters`` prints, then the class), which lands in the
        ``op_name`` of every instruction it traces
        (observability/scopes.py).  A layer nobody holds runs under its
        class name alone."""
        object.__setattr__(self, "_scope",
                           f"{attr}:{type(self).__name__}")

    def _scope_name(self) -> str:
        return self.__dict__.get("_scope") or type(self).__name__

    def register_buffer(self, name: str, tensor, persistable=True):
        t = tensor if isinstance(tensor, Tensor) or tensor is None else Tensor(tensor)
        self._buffers[name] = t
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return t

    # -- traversal ---------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def _named_persistable_buffers(self, prefix=""):
        """Like named_buffers, but each layer filters its OWN
        non-persistable buffers (so sublayer persistability is honored)."""
        for name, b in self._buffers.items():
            if b is not None and name not in self._non_persistable_buffer_names:
                yield (f"{prefix}.{name}" if prefix else name), b
        for lname, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{lname}" if prefix else lname
            yield from layer._named_persistable_buffers(sub_prefix)

    def children(self) -> Iterator["Layer"]:
        for l in self._sub_layers.values():
            if l is not None:
                yield l

    def named_children(self):
        for n, l in self._sub_layers.items():
            if l is not None:
                yield n, l

    def sublayers(self, include_self=False) -> List["Layer"]:
        out = [self] if include_self else []
        for l in self.children():
            out.append(l)
            out.extend(l.sublayers())
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for n, l in self.named_children():
            p = f"{prefix}.{n}" if prefix else n
            yield p, l
            yield from l.named_sublayers(p)

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- modes -------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.children():
            l.train()
        return self

    def eval(self):
        self.training = False
        for l in self.children():
            l.eval()
        return self

    # -- state dict --------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True) -> Dict[str, Tensor]:
        dest = destination if destination is not None else collections.OrderedDict()
        for n, p in self.named_parameters(structured_name_prefix.rstrip(".")):
            # a compiled step may hold the authoritative value elsewhere
            # (ZeRO-3 padded shards, LocalSGD replicas); let it refresh
            # p.data before we hand out a stale mirror
            owner = getattr(p, "_param_owner_step", None)
            owner = owner() if owner is not None else None
            if owner is not None:
                owner.sync_params()
            dest[n] = p
        for n, b in self._named_persistable_buffers(
                structured_name_prefix.rstrip(".")):
            dest[n] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Load values into existing parameters/buffers (shape-checked)."""
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            tgt = own[k]
            arr = v.data if isinstance(v, Tensor) else jnp.asarray(v)
            if tuple(arr.shape) != tgt.shape_tuple:
                raise ValueError(
                    f"shape mismatch for {k}: loading {list(arr.shape)} into "
                    f"{tgt.shape}")
            # copy: loaded params must not alias the source (donation-safe)
            tgt.data = jnp.array(arr, dtype=tgt.data.dtype, copy=True)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            d = convert_dtype(dtype)
            for p in self.parameters():
                p.data = p.data.astype(d)
            for b in self.buffers():
                if jnp.issubdtype(b.data.dtype, jnp.floating):
                    b.data = b.data.astype(d)
            self._dtype = d
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks & call ------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = _HookHandle(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        with jax.named_scope(self._scope_name()):
            out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for n, l in self._sub_layers.items():
            sub = repr(l).split("\n")
            sub = [sub[0]] + ["  " + s for s in sub[1:]]
            lines.append(f"  ({n}): " + "\n".join(sub))
        main = f"{type(self).__name__}({extra}" + ("" if not lines else "\n")
        if lines:
            main += "\n".join(lines) + "\n"
        return main + ")"

    def full_name(self):
        return self._name_scope

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


class _HookHandle:
    _next_id = 0

    def __init__(self, store):
        self.store = store
        self.id = _HookHandle._next_id
        _HookHandle._next_id += 1

    def remove(self):
        self.store.pop(self.id, None)
