"""Sampler plug-ins: what the harness knows about one sampler.

A configuration names its sampler in ``studies.sampler.name``; the
harness loads ``samplers/<name>.py`` beside this file by that name
(``registry.sampler``) and knows nothing else of the sampler.  A run of a
configuration whose sampler has no file fails at once.  A plug-in is
added with its file alone.

Importing a plug-in imports no JAX and starts nothing: ``bench/run.py``
imports it too, and never touches the chip.  A plug-in provides:

``SPANS: tuple[str, ...]``
    The names of the spans the sampler writes (``repro.core.tracing``).
    In a traced run ``bench/spans.py`` reads them beside the service's
    own, and ``bench/devtrace.py`` leaves them out of the host events it
    puts the device's idle gaps down to.

In the launcher (``bench/serve.py``, the process that owns the chip),
in this order, before the service starts:

``control_high() -> None``
    With ``--control high``: puts the sampler's plain scoring, computed
    one precision step below the one its kernels state, in the program's
    place.  The check has to come out false under it.
``FAULTS: dict[str, Callable[[], None]]``
    Faults beneath the sampler's own path, by the name ``--fault`` gives;
    each patches the program when called.  The launcher plants the faults
    that are not about one sampler itself.
``warm_shapes(plan: dict) -> set``
    The shape of every sampler program call that the run's schedule can
    reach, by the sampler's own bucketing rules, from the plan
    ``bench/run.py`` wrote (``studies``, ``ranges``: per study the fewest
    and most observations an ask can see, ``batch``).
``warm(shapes: set) -> None``
    Compiles and runs the program once at each of those shapes, built as
    the sampler builds its buffers, so that nothing compiles in the
    window.
``Recorder(seed: int, sample: int)``
    Records the sampler's calls inside the window.  ``install()`` wraps
    the program (after the control and the faults are in place);
    ``active`` is set true for the window and false after it;
    ``shapes`` lists one shape tuple per call in the window, which the
    per-layer metrics read as ``call_shapes``; ``unmatched`` counts calls
    it could not pair with their inputs; ``save(path) -> int`` writes a
    sample of at most ``sample`` calls drawn from ``seed`` and returns
    how many it wrote.  The sample goes to ``calls.npz``: ``n``, the
    number of calls, and one array ``<i>_<field>`` per field of call
    ``i``.  Until the window has closed a recorded call may keep only
    small device buffers (outputs, masks, the rows a split chose) on the
    device; its large inputs are host arrays the program does not change
    in place.  Everything leaves the device in ``save``.

In the harness (``bench/run.py``), after the window:

``load(path: str) -> list[dict]``
    The calls ``save`` wrote, one dict of host arrays each.
``check(calls, known, history, served) -> dict[str, float | int]``
    Each call against the plain reference (a module of the benchmark
    that imports nothing of the program).  ``known`` maps every value a
    completed trial of the run can hold to its point on the unit cube,
    ``history`` is the set of the history's values, ``served`` the unit
    points of every served trial.  Each number returned is judged against
    the configuration's ``correct`` limit of the same name, 0 where it
    names none.
``top(call: dict) -> np.ndarray``
    The unit-cube point the call served; the harness looks for it among
    the proposals the service answered with.
"""
