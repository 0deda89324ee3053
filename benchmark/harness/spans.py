"""Host spans recorded from the benchmark's own files, around the calls into
the program's layers, in the profiler's own trace (``TraceAnnotation``).

The wrappers are installed in EVERY run and record only in a traced one: a
Pallas program's compile-cache key carries the innermost frames of the call
stack it was traced under, so a wrapper that exists only in the traced run
gives that run programs of its own, and a compile of some 290 s; so does a
wrapper that calls the program from another LINE in a traced run (my chip
runs, PR 24).  A call the program no longer has is skipped: the gap then
falls to the enclosing ``bench_iteration`` span."""

import contextlib
import functools
import importlib

# (module, class, method, span name)
PROGRAM_CALLS = [
    ("lightgbm_tpu.boosting.gbdt", "GBDT", "_flush_pending", "host_flush_assemble"),
    ("lightgbm_tpu.boosting.gbdt", "GBDT", "_train_trees_fused", "host_dispatch_fused"),
    ("lightgbm_tpu.boosting.gbdt", "GBDT", "_train_trees", "host_train_trees_sync"),
    ("lightgbm_tpu.boosting.gbdt", "GBDT", "_compute_gradients", "host_gradients"),
    ("lightgbm_tpu.engine", "Booster", "eval_valid", "host_eval_valid"),
    ("lightgbm_tpu.engine", "Booster", "update", "host_booster_update"),
]


RECORD = False      # set by the traced run


def wrap_program_calls():
    import jax
    for mod_name, cls_name, meth, span in PROGRAM_CALLS:
        try:
            cls = getattr(importlib.import_module(mod_name), cls_name)
            fn = getattr(cls, meth)
        except (ImportError, AttributeError):
            continue
        if getattr(fn, "_bench_span", None):
            continue

        def wrapped(*a, _fn=fn, _span=span, **kw):
            # ONE call site for both kinds of run: the line is in the key too
            with (jax.profiler.TraceAnnotation(_span) if RECORD
                  else contextlib.nullcontext()):
                return _fn(*a, **kw)

        functools.update_wrapper(wrapped, fn)
        wrapped._bench_span = span
        setattr(cls, meth, wrapped)
