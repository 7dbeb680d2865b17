"""What a gradient program keeps for its backward pass, and what it
recomputes.

The model's layer scans are checkpointed (``ModelConfig.remat``), so by
default the backward pass runs each layer's forward a second time. In
prompt tuning the weights are frozen, and what that second forward
produces for the backward pass are the projection outputs: attention's
q, k and v and the FFN's gate and up, tagged with ``checkpoint_name`` in
``repro.models``. Keeping them costs device memory instead.

:class:`GradProgram` compiles a gradient program on the first rung of
:data:`SAVE_LADDER` that fits: the compile does not run out of device
memory (``RESOURCE_EXHAUSTED``), and where the device reports its free
memory, the program's temporaries and outputs fit in it. A rung that
does not fit moves the program one rung down. The rung that fitted is
remembered per model configuration, program, argument signature and
device kind for the life of the process, so that a later program of the
same kind, such as the next job's tuner, starts there. Each program
compiled is counted in ``repro.obs.device`` under its rung (1-based).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Hashable, Tuple

import jax

from repro.obs import device

# Names the backward pass reads instead of recomputing, most first; the
# last rung keeps nothing (full rematerialisation).
SAVE_LADDER: Tuple[Tuple[str, ...], ...] = (
    ("q", "k", "v", "gate", "up"),
    ("gate", "up"),
    (),
)

# (model config, program key, argument signature, device kind) -> the
# index of the first rung that fitted
_RUNGS: Dict[tuple, int] = {}


def _device_kind(leaves) -> str:
    """The kind of device the arguments are placed on (the default
    device's when none is)."""
    for x in leaves:
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            return next(iter(sharding.device_set)).device_kind
    return jax.devices()[0].device_kind


def _fits_free_memory(compiled, leaves) -> bool:
    """The program's temporaries and outputs fit in what the arguments'
    devices have free now, where they say: the arguments are arrays
    (taken to be resident) on devices that report their memory."""
    arrays = [x for x in leaves if isinstance(x, jax.Array)]
    if not arrays:
        return True
    mem = compiled.memory_analysis()
    need = (mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes)
    for d in arrays[0].sharding.device_set:
        stats = d.memory_stats() or {}
        if ("bytes_limit" in stats
                and need > stats["bytes_limit"] - stats["bytes_in_use"]):
            return False
    return True


class GradProgram:
    """A jitted gradient program on the first rung of :data:`SAVE_LADDER`
    that fits, chosen per argument signature at its first call or
    :meth:`lower`.

    ``build(model)`` returns the step function over ``model``, which is
    ``model.saving(names)`` for each rung tried. ``key`` names what
    besides the model configuration and the arguments decides the
    program's memory (the program and its settings; hashable).

    Traced inside another program (its arguments are tracers), the
    program is the last rung's, as that one's compile decides what fits.
    """

    def __init__(self, build: Callable, model, key: Hashable):
        self.build, self.model, self.key = build, model, key
        self._rung_jits: Dict[int, Any] = {}     # rung index -> jax.jit
        self._chosen: Dict[tuple, Any] = {}      # signature -> jax.jit

    def __call__(self, *args):
        return self._jitted(args)(*args)

    def lower(self, *args):
        """The chosen rung's ``Lowered``; its ``compile()`` returns the
        program already compiled."""
        return self._jitted(args).lower(*args)

    def _jit(self, rung: int):
        jitted = self._rung_jits.get(rung)
        if jitted is None:
            jitted = self._rung_jits[rung] = jax.jit(
                self.build(self.model.saving(SAVE_LADDER[rung])))
        return jitted

    def _jitted(self, args):
        leaves, tree = jax.tree_util.tree_flatten(args)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            return self._jit(len(SAVE_LADDER) - 1)
        sig = (tree, tuple((x.shape, x.dtype, getattr(x, "sharding", None))
                           for x in leaves))
        jitted = self._chosen.get(sig)
        if jitted is None:
            jitted = self._chosen[sig] = self._climb(sig, args, leaves)
        return jitted

    def _climb(self, sig, args, leaves):
        where = (self.model.cfg, self.key, sig, _device_kind(leaves))
        last = len(SAVE_LADDER) - 1
        rung = _RUNGS.get(where, 0)
        while True:
            try:
                compiled = self._jit(rung).lower(*args).compile()
                fits = rung == last or _fits_free_memory(compiled, leaves)
            except jax.errors.JaxRuntimeError as e:
                if rung == last or "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                fits = False
            if fits:
                break
            rung += 1
        _RUNGS[where] = rung
        device.grad_program(
            rung + 1, compiled.memory_analysis().temp_size_in_bytes)
        return self._jit(rung)
