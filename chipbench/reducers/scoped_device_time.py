"""Device time per execution of a compiled program, in ms, spent in the
operations of one phase: those whose ``jax.named_scope`` stack holds one of
``"scope"`` and none of ``"exclude"`` (chip 0; the union of their intervals,
so overlapping operations are not counted twice).

``{"program_prefix": "jit_pure_step(", "site": "spmd.step",
"scope": ["spmd.optimizer"], "exclude": []}``: the program by its traced
name, its text by the compile site that serves it.  ``"scope": [""]`` selects
every operation, so with the other phases' scopes under ``"exclude"`` it
reads what they leave: the step's remaining busy time.  A per-block share is
a file away (``"scope": ["/bertencoder0/"]``).

How an operation gets its scope, and the two rules (a fusion counts under its
root; recomputed forward counts as backward), are in ``trace_scopes.py``.
Where the program serves no text the metric is left out."""
from .. import trace_scopes


def reduce(arguments, ctx, result, trace):
    if trace is None or not trace.devices:
        return None
    runs = trace_scopes.executions(trace, arguments["program_prefix"])
    scopes = trace_scopes.program_scopes(arguments["site"]) if runs else None
    if not scopes:
        return None
    ops = trace_scopes.ops_of(trace, runs)
    known = sum(1 for name in {ev[2] for ev in ops}
                if trace_scopes.instruction_name(name) in scopes)
    if not known:
        return None  # another program's text: nothing joins
    chosen = trace_scopes.selected(ops, scopes, arguments["scope"],
                                   arguments.get("exclude", ()))
    return trace_scopes.busy_ms_per_execution(chosen, runs)
