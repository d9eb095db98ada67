"""Model family drivers: what the harness needs to know of one kind of model.

A configuration file names its family (``"family": "vision"``), and
``spec.load_family`` loads ``bench/families/<family>.py``.  The runner
(``bench/run.py`` ``run_cell``) knows no family: it owns the chip refusal,
the compile cache, the ``setup_s`` clock, the measured window's tracing rules
(:class:`run.Window`), the memory peak, the trace and the per-layer readers,
``setup_s`` and ``latency_p50_ms``, and the result line.  A family driver is
a module with these functions; ``cfg`` is the configuration file as a dict,
``traffic`` the traffic mix, ``seed`` the run's seed:

``make_weights(cfg, seed)``
    The seeded weights, on the device: a pytree of arrays, made in one jitted
    call from the seed.

``pool(cfg, traffic, seed)``
    The host-side inputs the window sends, from the seed.

``build(cfg, weights, traffic, pool, *, interpret, fault)``
    ``(driven, compiled)``: what the window drives, and the compiled
    executables it uses as ``{name: jax.stages.Compiled}`` (one for a vision
    executor; a prefill chunk and a decode step for an LM).  Every shape the
    window uses is warmed before it returns.  ``interpret`` runs Pallas
    kernels in interpret mode (tests on the CPU).  ``fault``, where it is not
    None, takes the family's compiled executable and returns what the window
    drives in its place (the control, and tests that break the timed path);
    warm-up goes through it.  Array leaves of ``driven`` that are not leaves
    of ``weights`` are the program's: the runner frees them before the check.

``serve(cfg, driven, pool, traffic, seed, window)``
    The family's request loop over ``window.due(offsets)``, each request
    inside ``with window.request():``.  Returns one row per request that
    starts ``(key, due, done, output)``: ``due`` when the request was due and
    ``done`` when its result was on the host, on ``time.perf_counter``'s
    clock; readers may use further fields.

``attempted(traffic, rows)``
    The answers the window was asked for (the result's ``attempted``).

``end_to_end(name, ctx)``
    The family's own end-to-end metrics (``setup_s`` and ``latency_p50_ms``
    are the runner's); a ``KeyError`` for a name it does not know.

``check(cfg, traffic, seed, weights, pool, rows)``
    ``({number: (value, limit)}, extras)``: what the window served against
    the family's own plain reference, which imports nothing of the program.
    ``extras`` is a dict that the result carries under ``extras``.

``context(cfg, traffic)``
    The ``ctx`` entries that the family's per-layer readers need.

``control(cfg, seed, traffic)``
    A ``fault`` hook that puts the plain reference one precision below the
    configuration's in the program's place (``bench/control.py``).

``rehearse(cfg, sizes, device)``
    Compile what the window drives for a described chip (``device``) at each
    of ``sizes`` and print what the compiler says (``bench/rehearse.py``).

Adding a family is adding files: this driver, its reference (under
``benchlib/`` or beside the driver), its configurations with ``family``, its
traffic mixes and its per-layer readers, and their entries in
``BENCHMARK.json``.
"""
