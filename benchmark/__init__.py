"""The yardstick of paddle_tpu: one command runs one cell of
``BENCHMARK.json`` once and prints one JSON line (see ``run.py``).

Everything that decides a number lives here, where a PR that claims a
gain may not change it: traffic generation (``traffic.py``), the
arithmetic from events to metrics (``metrics.py``), the chip's published
peaks (``flops.py``), the reduction of a profiler trace (``trace.py``),
what the benchmark knows of each family of model, its counts of
parameters, operations and bytes among it (``models/``), the plain
reference each configuration is held to (``reference/``), the widths each
was published at (``published/``) and the comparison that decides
``correct`` (``runners/``). From the program it takes the system under
test, its ``stats()`` counters and the names its kernels carry in a trace.

A cell, a configuration, a traffic mix, a layer metric, a kind of run, a
family of model, a reference and a table of published widths are each a
file found by the name ``BENCHMARK.json`` or the configuration gives.
Nothing here holds a table of them, and the code every configuration
shares (``run.py``, ``harness.py``, ``rehearse.py``, ``runners/``) names
no model and no parameter. So a later PR adds a configuration, of another
architecture too, by adding files and entries, and edits no file that is
there. What it brings:

``configs/<c>.json``
    the configuration as it is run: the published keys under their
    published names; ``model``, ``reference`` and ``published`` (the three
    names below); ``reduced`` (the COUNTS cut from the source: depth,
    leading dense layers, routed experts held, vocabulary rows,
    next-token-prediction modules; never a width), with ``period`` and
    ``published_counts`` where more than depth is cut; ``assumed`` (sizes
    the source does not give); ``deployment`` (what it stands for, and
    over how many chips a layer is shared); ``oracle`` (each tolerance of
    its runner's check, with the ``reason`` for it: it follows from this
    model's depth and precision); and the settings of its kind of run
    (``program``, ``engine`` or ``trainer``).
``published/<p>.json``
    ``{"source": ..., "config": {...}}``: the source's ``config.json`` as
    known. The contract tests hold every key of it to the configuration
    file unless ``reduced`` lists the key, and the cut to the guide's
    floors.
``models/<m>.py``
    the family, for the runners: ``program_config(config, **program)``,
    ``make_weights(cfg, seed, shardings=None)`` (on the device, one jitted
    call, from the seed), ``vocab_size(config)``, ``param_shapes(cfg)``;
    for a training cell ``train_step(cfg, lr) -> (init_opt, step)``,
    ``loss_fn(params, ids, labels, cfg)``, ``param_specs(cfg, mp_axis)``,
    ``batch_spec(axes)``; and its counts, from the configuration file:
    ``total_params``, ``matmul_params``, ``train_flops_per_token(config,
    seq)``, ``cache_bytes_per_token(config)``.
``reference/<r>.py``
    the architecture in plain float32 ``jax.numpy``, importing nothing of
    the program. For a serving cell's oracle it walks the PROGRAM's
    parameter tree: ``embed(params, ids)``, ``n_blocks(params)``,
    ``block(params, i, x, config)`` (one block's weights upcast alone; a
    block need not be like its neighbour), ``head(params, x, config)``.
    For a training cell's check: ``from_program(params)`` and
    ``loss_and_grad_norm(params, ids, labels, config)``. What is compared
    and how stays the runner's.
``traffic/<t>.json``
    the mix's parameters, read by the one generator; ``kind`` names the
    runner (``runners/<kind>.py``).
``layer_metrics/<metric>.py``
    ``LAYER``, ``MOVES``, ``UNIT`` and ``read(run)``; a reader that finds
    nothing to read returns ``None``.
``BENCHMARK.json``
    the ``configs`` and ``workloads`` entries; the new cell's name
    APPENDED to the ``workloads`` list of every end-to-end metric it
    reports and of every reader that reads in it (a reader's list names
    the cells it finds something to read in, not the one it was written
    for); and a ``per_layer`` entry for each new reader, APPENDED at the
    end of ``per_layer``, after whatever is last then. Append, never
    insert: an entry that is there keeps its index, because the driver
    reads an entry put in the middle as a change to the one whose place
    it takes. That is all the tests hold of the order, and of a
    reader's ``workloads`` they hold which cells are AMONG it: none pins
    the end of ``per_layer`` or a reader's list to one cell.
``tests/benchmark/test_benchmark_<family>.py``
    the family's tests. What the file holds of the repo's
    ``BENCHMARK.json`` (which readers its cell lists, where its own
    entries stand: right after the entry that was last before them,
    whatever follows; its cell FIRST in their ``workloads``, whatever
    follows) it brings as a function ``check_*(bench)``, and its ``test_``
    of the same matter takes the ``bench`` fixture and hands it over.

How the last two are held (PR 27's tests pinned the tail, PR 32 freed it,
PR 33's own file pinned it and its readers' lists again, PR 35 freed those
and closed the way in). THE MARKER: a function of a
``tests/benchmark/test_benchmark_*.py`` whose name starts with ``check_``
and whose first parameter is named ``bench`` is a structural check; it
takes ``(bench)`` or ``(bench, roots)``.
``test_benchmark_contract.py`` does to a copy of the repo's file what the
next ``model_config`` PR does (a configuration, a cell, its name on
``out_tokens_per_s`` and on every reader it reads in, four new entries at
the end; once in memory, once for real in a temporary directory) and
calls EVERY function that bears the marker on the result
(``structural_checks`` finds them, no list names them), so a family file
that a later PR adds is walked by being there. THE RULE, which
``test_no_test_goes_round_the_rehearsal`` reads off the sources: the
repo's ``BENCHMARK.json`` is opened by the one ``bench`` fixture
(``tests/benchmark/conftest.py``; ``part_of`` reads the configurations'
names and ``paths`` while cases are collected), a ``test_`` does nothing
with it but hand it to a function that bears the marker, nothing counts
``per_layer`` from its end, and nothing holds a ``workloads`` equal to a
list. A family file written by copying the last one passes its
assertions through the rehearsal or fails that test in the PR that adds
it.

``tests/benchmark/rehearsal/`` is the proof on the CPU: its ``toy.train``
cell runs a model this directory has no file for, through
``runners/train.py``, from files of that tree alone.
"""
