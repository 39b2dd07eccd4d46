"""Tensor: a mutable facade over jax.Array.

Analog of phi::DenseTensor + the eager AutogradMeta (paddle/phi/core/
dense_tensor.h:38, fluid/eager/autograd_meta.h): holds a device array, a
stop_gradient bit (paddle semantics: True by default, False for Parameters),
an optional .grad, and a link to the tape Node that produced it. In-place ops
rebind the wrapped array — mutation lives in the wrapper, the arrays stay
immutable, which is exactly what makes the same object traceable under jit via
the functional overlay (core/functional.py).
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from . import functional as _functional
from .dtype import convert_dtype, from_jax_dtype, to_jax_dtype
from .place import CPUPlace, Place, TPUPlace, current_place

_uid_counter = itertools.count()


class Tensor:
    """Eager tensor. Wraps one jax.Array; methods are bound by paddle_tpu.ops."""

    # populated by paddle_tpu.ops._bind_tensor_methods
    _method_registry = {}

    def __init__(self, value, stop_gradient: bool = True, name: str = None):
        if isinstance(value, Tensor):
            value = value._value
        elif not isinstance(value, jax.Array):
            value = jnp.asarray(value)
        self._v = value
        self._uid = next(_uid_counter)
        self.stop_gradient = stop_gradient
        self.grad = None
        self.name = name or f"tensor_{self._uid}"
        self.persistable = False
        self._grad_node = None  # tape Node that produced this tensor
        self._out_index = 0
        self._hooks = []
        self._tape_requires = False

    # ---- value resolution (overlay-aware) ----
    @property
    def _value(self):
        ov = _functional.overlay_get(self._uid)
        return ov if ov is not None else self._v

    def _set_value_raw(self, arr):
        if not _functional.overlay_set(self._uid, arr):
            self._v = arr

    # ---- basic metadata ----
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self):
        return from_jax_dtype(self._value.dtype)

    def _jdtype(self):
        return self._value.dtype

    @property
    def place(self) -> Place:
        v = self._value
        if not isinstance(v, jax.Array):
            return CPUPlace(0)  # host (numpy) value
        if isinstance(v, jax.core.Tracer):
            return current_place()  # a traced value lives on no device yet
        dev = next(iter(v.devices()))
        return TPUPlace(dev.id) if dev.platform == "tpu" else CPUPlace(dev.id)

    @property
    def is_leaf(self) -> bool:
        return self._grad_node is None

    def __deepcopy__(self, memo):
        # fresh uid + name: overlay keys and optimizer-state keys must stay
        # unique per live tensor (deepcopied transformer layers would otherwise
        # collide in Optimizer.state_dict, which keys accumulators by name)
        cls = type(self)
        new = cls.__new__(cls)
        new.__dict__.update(self.__dict__)
        new._v = self._value
        new._uid = next(_uid_counter)
        new.name = f"{self.name}@copy{new._uid}"
        new._grad_node = None
        new._hooks = []
        new.grad = None
        memo[id(self)] = new
        return new

    # jax interop: jnp.asarray(tensor) works via this protocol
    def __jax_array__(self):
        return self._value

    def __array__(self, dtype=None):
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype is not None else arr

    def numpy(self):
        return np.asarray(self._value)

    def item(self, *args):
        return self._value.item(*args)

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __repr__(self):
        grad_part = "" if self.stop_gradient else ", stop_gradient=False"
        return (
            f"Tensor(shape={self.shape}, dtype={self.dtype.name}{grad_part},\n"
            f"       {np.asarray(self._value)})"
        )

    def __bool__(self):
        return bool(self._value)

    def __int__(self):
        return int(self._value)

    def __float__(self):
        return float(self._value)

    def __index__(self):
        # lets scalar int Tensors drive range()/slicing eagerly; under a
        # trace this raises TracerIntegerConversionError, which to_static
        # catches to trigger dy2static AST conversion
        if not jnp.issubdtype(self._value.dtype, jnp.integer):
            raise TypeError(
                f"only integer Tensors can be used as an index, got {self._value.dtype}")
        return int(self._value)

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return repr(self)

    # ---- graph / grad management ----
    def _attach(self, node, index: int = 0):
        self._grad_node = node
        self._out_index = index
        if node is not None:
            self.stop_gradient = False
        return self

    def _accumulate_grad(self, g):
        g = g if isinstance(g, jax.Array) else jnp.asarray(g)
        if g.dtype != self._value.dtype and jnp.issubdtype(self._value.dtype, jnp.inexact):
            g = g.astype(self._value.dtype)
        for hook in self._hooks:
            out = hook(Tensor(g, stop_gradient=True))
            if out is not None:
                g = out._value if isinstance(out, Tensor) else jnp.asarray(out)
        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True)
            self.grad.name = self.name + "@GRAD"
        else:
            self.grad._v = self.grad._v + g

    def backward(self, grad_tensor=None, retain_graph: bool = False):
        from . import autograd

        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def register_hook(self, hook):
        """Hook on this tensor's gradient (fluid/eager hooks analog)."""
        if self._grad_node is not None:
            self._grad_node.add_hook(self._out_index, hook)
            node, idx = self._grad_node, self._out_index

            class _Handle:
                def remove(self_inner):
                    node.hooks.get(idx, []).remove(hook)

            return _Handle()
        self._hooks.append(hook)
        hooks = self._hooks

        class _Handle:
            def remove(self_inner):
                hooks.remove(hook)

        return _Handle()

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self.grad is not None:
            self.grad._v = jnp.zeros_like(self.grad._v)
        else:
            self.grad = None

    def detach(self) -> "Tensor":
        t = Tensor(self._value, stop_gradient=True, name=self.name + "@detached")
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    # ---- mutation (rebinds the wrapped array) ----
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._value
        arr = jnp.asarray(value)
        if tuple(arr.shape) != tuple(self._value.shape):
            raise ValueError(f"set_value shape mismatch: {arr.shape} vs {tuple(self._value.shape)}")
        self._set_value_raw(arr.astype(self._value.dtype))
        return self

    def copy_(self, other, blocking: bool = True):
        return self.set_value(other)

    def _inplace_from(self, result: "Tensor"):
        """Adopt another tensor's value+tape link (used by x.add_(y) etc.)."""
        self._set_value_raw(result._value)
        self._grad_node = result._grad_node
        self._out_index = result._out_index
        self.stop_gradient = result.stop_gradient
        return self

    def to(self, *args, **kwargs):
        """to(dtype) / to(place) / to(device_str)."""
        out = self
        for arg in list(args) + list(kwargs.values()):
            if isinstance(arg, Place):
                out = Tensor(jax.device_put(out._value, arg.jax_device()), stop_gradient=out.stop_gradient)
            elif isinstance(arg, str) and arg.split(":")[0] in ("cpu", "tpu", "gpu", "cuda"):
                from .place import set_device, current_place

                prev = current_place()
                p = set_device(arg)
                set_device(prev)
                out = Tensor(jax.device_put(out._value, p.jax_device()), stop_gradient=out.stop_gradient)
            else:
                out = out.astype(arg)
        return out

    def cpu(self):
        return Tensor(jax.device_put(self._value, jax.devices("cpu")[0]), stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    def cuda(self, device_id=0):  # parity alias: moves to the accelerator
        return self.to(TPUPlace(device_id))

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    # ---- indexing (differentiable path lives in ops; bound late) ----
    def __getitem__(self, idx):
        return Tensor._method_registry["__getitem__"](self, idx)

    def __setitem__(self, idx, value):
        return Tensor._method_registry["__setitem__"](self, idx, value)

    def __getattr__(self, name):
        registry = Tensor._method_registry
        if name in registry:
            fn = registry[name]
            return lambda *args, **kwargs: fn(self, *args, **kwargs)
        raise AttributeError(f"'Tensor' object has no attribute {name!r}")

    def astype(self, dtype):
        return Tensor._method_registry["astype"](self, dtype)

    @property
    def T(self):
        return Tensor._method_registry["t"](self)

    # value_and-place helpers used by framework internals
    def block_until_ready(self):
        self._value.block_until_ready()
        return self

    def value(self):
        return self

    def get_tensor(self):
        return self


class Parameter(Tensor):
    """Trainable tensor (paddle.nn.Parameter / phi DenseTensor + persistable)."""

    def __init__(self, value, trainable: bool = True, name: str = None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable
        self.is_distributed = False
        self.dist_spec = None  # PartitionSpec-like annotation for GSPMD sharding
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor analog."""
    if isinstance(data, Tensor):
        arr = data._value
    elif isinstance(data, jax.Array):
        arr = data
    else:
        np_arr = np.asarray(data)
        if dtype is None and np_arr.dtype == np.float64:
            np_arr = np_arr.astype(np.float32)  # paddle default_dtype semantics
        arr = jnp.asarray(np_arr)
    if dtype is not None:
        arr = arr.astype(to_jax_dtype(convert_dtype(dtype)))
    if place is not None and isinstance(place, Place):
        arr = jax.device_put(arr, place.jax_device())
    return Tensor(arr, stop_gradient=stop_gradient)
