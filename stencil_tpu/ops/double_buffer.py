"""Ping-pong buffers that keep their slots inside compiled code.

A double-buffered step reads ``curr``, writes the new state into the buffer
that came in as ``nxt`` (the Pallas kernels alias it to their result) and
hands the pair back exchanged: ``step((curr, nxt)) -> (new_curr, new_nxt)``
with ``new_curr`` living where ``nxt`` did. The reference does the same
with a pointer swap on the host (src/local_domain.cu:67-84,
astaroth.cu:642-648). Compiled code has no pointers to swap:

- a ``while`` keeps carry slot *i* in one buffer for the whole loop, so a
  trip that returns its two carries exchanged costs three whole-buffer
  copies (one through a temporary);
- ``donate_argnums`` pairs donated input *i* with output *i*, so a program
  that returns its two buffers exchanged pays the same three copies at its
  root, loop or no loop.

So here a trip runs TWO steps and ends where it began (:func:`repeat`), the
jitted program returns the pair in the order it came in whatever the parity
of the steps it ran, and the swap an odd count leaves is a swap of two
handles on the host (:func:`jit_in_place`). This module is the only place
that knows the parity.
"""

from __future__ import annotations

from typing import Sequence

from jax import lax

from ..obs import scopes, telemetry


def repeat(step, n: int, pair):
    """``n`` applications of ``step`` to a ping-pong ``pair`` in traced
    code: ``n // 2`` ``while`` trips of two steps (one trip alone runs
    without a ``while``), then the odd step. Returns the pair as ``step``
    does: new state first."""
    trips, tail = divmod(n, 2)
    if trips == 1:
        pair = step(step(pair))
    elif trips:
        pair = lax.fori_loop(0, trips, lambda _, p: step(step(p)), pair)
    if tail:
        pair = step(pair)
    return pair


class InPlaceLoop:
    """``loop(curr, nxt, *rest) -> (new_curr, new_nxt)`` over a jitted
    ``program`` that returns the two donated buffers in their input slots:
    with ``host_swap`` the new state left in the second slot, and the two
    handles are exchanged here. ``trace`` and ``lower`` are the jitted
    program's own (jax's ``stages.Wrapped`` protocol, so ``jax.export``
    takes the loop as it takes a jitted function)."""

    def __init__(self, program, host_swap: bool):
        self.program = program
        self.host_swap = host_swap

    def __call__(self, curr, nxt, *rest):
        first, second = self.program(curr, nxt, *rest)
        return (second, first) if self.host_swap else (first, second)

    def trace(self, *args, **kwargs):
        return self.program.trace(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self.program.lower(*args, **kwargs)


def jit_in_place(module: str, fn, args, counts: Sequence[int]) -> InPlaceLoop:
    """Jit ``fn(curr, nxt, *rest) -> (new_curr, new_nxt)`` under the module
    name ``module`` (registered with ``args``, see ``scopes.jit_loop``) with
    both buffers donated. ``counts`` are the static step counts of the
    :func:`repeat` runs ``fn`` makes, in order (a bare step is a run of 1);
    their sum is the number of exchanging steps, and ``fn`` returns the new
    state first as a chain of such steps does. One ``loop.pingpong`` counter
    per build says what was built."""
    steps = sum(counts)
    trips = sum(n // 2 for n in counts)
    host_swap = bool(steps % 2)

    def program(curr, nxt, *rest):
        new_curr, new_nxt = fn(curr, nxt, *rest)
        return (new_nxt, new_curr) if host_swap else (new_curr, new_nxt)

    jitted = scopes.jit_loop(module, program, args, donate_argnums=(0, 1))
    telemetry.get().counter(
        "loop.pingpong", value=steps, phase="compute", module=module,
        steps=steps, steps_per_trip=2, trips=trips,
        tail_steps=steps - 2 * trips, host_swap=host_swap)
    return InPlaceLoop(jitted, host_swap)
