"""The one way into the profiler's trace: :func:`span`."""
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
