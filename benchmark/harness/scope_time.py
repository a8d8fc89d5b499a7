"""Device time under the program's own names.

The trace gives seconds per compiled instruction (``fusion.2345``); the
program gives, for the programs it dispatched, each instruction's
``op_name`` (``paddle_tpu.monitor.devscope.scope_maps``) and what an
``op_name`` says (``devscope.classify``: a phase -- forward, backward,
recompute, grad_sync, optimizer -- and the innermost ``jax.named_scope`` of
the program's vocabulary, or none).  Joined here into seconds per
(phase, scope), mean over devices.  An instruction that no registered
program holds has phase ``unmapped``.

A program without ``monitor.devscope`` (an earlier commit) gives no table,
and the readers built on it return nothing.  The table is made once per
reduced trace, whichever reader asks first, and printed then.

Inexact by construction: a fusion that spans two scopes carries the
``op_name`` of one of its instructions and is counted under that one (on the
v5e ResNet's convolution fusions hold batch-norm reductions, PERF.md
section 5); what XLA adds itself (a prefetch's ``copy-done``, a layout
copy) is counted with the instruction that consumes its result.
"""

import time
import weakref

UNMAPPED = "unmapped"

_tables = weakref.WeakKeyDictionary()        # Reduced -> {(phase, scope): s}


def seconds(trace, cell):
    """``{(phase, scope): seconds}``, or None without a trace or without
    the program's part."""
    if not trace:
        return None
    if trace not in _tables:
        _tables[trace] = _join(trace, cell)
    return _tables[trace]


def _join(trace, cell):
    try:
        from paddle_tpu.monitor import devscope
    except ImportError:
        return None
    t0 = time.perf_counter()
    maps = devscope.scope_maps()
    took = time.perf_counter() - t0
    classes, twice = {}, set()
    for names in maps.values():
        for name, op_name in names.items():
            cls = devscope.classify(op_name)
            if classes.setdefault(name, cls) != cls:
                twice.add(name)    # two programs disagree: nobody's
    for name in twice:
        classes[name] = (UNMAPPED, None)
    table = {}
    for d in trace.devices:
        for name, ns in d["by_name"].items():
            key = classes.get(name, (UNMAPPED, None))
            table[key] = table.get(key, 0.0) + ns / len(trace.devices) / 1e9
    _say(cell["say"], maps, took, table, trace.busy_s)
    return table


def _say(say, maps, took, table, busy_s):
    say("scope map: %d instructions of %d program(s) (%s) in %.3f s"
        % (sum(map(len, maps.values())), len(maps), ", ".join(maps), took))
    say("device seconds by phase and scope (share of busy %.6f s):" % busy_s)
    for (phase, scope), s in sorted(table.items(), key=lambda kv: -kv[1]):
        say("  %-10s %-11s %10.6f  %7.3f %%"
            % (phase, scope or "-", s, 100.0 * s / busy_s))
    total = sum(table.values())
    say("  %-22s %10.6f  %7.3f %%" % ("sum", total, 100.0 * total / busy_s))


def share(trace, cell, pick):
    """Per cent of the device's busy time in the rows ``pick(phase, scope)``
    takes: summed operation time over ``busy_s``, as ``flash_time_share``
    has it."""
    table = seconds(trace, cell)
    if table is None:
        return None
    return 100.0 * sum(s for key, s in table.items()
                       if pick(*key)) / trace.busy_s
