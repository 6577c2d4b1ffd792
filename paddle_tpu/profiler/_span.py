"""The two ways into the profiler's trace: :func:`span` names host time,
:func:`scope` names device time."""
import jax
from jax.profiler import TraceAnnotation


def span(name, **ids):
    """A host span ``name`` on the clock of the profile being taken
    (``jax.profiler.start_trace``, the ``.xplane.pb``'s host plane), to
    be used as ``with span("pt:<layer>.<phase>", step_id=7): ...``.

    The program's own spans are named ``pt:<layer>.<phase>``; ``ids`` are
    small integers that ride as the event's stats (``step_id``, ``rows``).
    With no profile running this is an idle TraceMe: no clock read of our
    own, no string formatting, no lock — callers must not read a clock to
    build ``ids`` either."""
    return TraceAnnotation(name, **ids)


def scope(name):
    """The device-side twin of :func:`span`, to be used as ``with
    scope("pt.core"): ...``: every operation LOWERED inside it carries
    ``name`` as one more token of its ``op_name`` (``jit(fused_step)/
    layers/3/self_attn/pt.core/dot_general``), through ``jit``, the bodies
    of ``scan`` / ``while``, ``jvp`` / ``transpose`` (the backward of a
    scope reads ``transpose(jvp(name))``) and ``checkpoint``, and a fusion
    takes its root's. A profile's device operations are then read by
    component (``benchmark/harness/components.py``).

    It is trace-time metadata, like a source line: no clock, no host work
    a step and no device instruction, and so no "off" state. In eager code
    it is one context manager entered and left.

    Grammar: a layer's token is the name its parent registered it under
    (``self_attn``, ``mlp``, ``q_proj``, a ``LayerList``'s index; the class
    name for a root), written by ``Layer.__call__``; what is not a layer is
    ``pt.<part>`` from a fixed vocabulary (``docs/architecture.md``,
    "Reading a trace"), written where the work is lowered and nested
    inside the layer that runs it."""
    return jax.named_scope(name)
