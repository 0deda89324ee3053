"""The names the program gives its own work.

Two kinds, both read by the benchmark (``benchmark/phases.json`` is held
equal to these tuples by a test) and by anyone who opens a profiler trace:

  * **Device scopes** (``jax.named_scope``): a scope is metadata only — it
    becomes a component of every enclosed operation's ``op_name``
    (``jit(step)/grow/partition/sort``), which the profiler writes next to
    each device event (the ``tf_op`` stat of the event's metadata in the
    ``.xplane.pb``).  It adds no equation to the traced program.
  * **Host spans** (``trace.span``): ``jax.profiler.TraceAnnotation`` events
    named ``lgbt.<span>`` in the same ``.xplane.pb``, on the same clock as
    the device events.

Every ``named_scope`` call in the program goes through :func:`scope`, so a
name that is not listed here cannot reach a trace.
"""

from __future__ import annotations

import jax

# top-level phases of one fused boosting iteration, in program order
DEVICE_PHASES = ("gradients", "root", "opening", "grow", "replay", "emit",
                 "score_update")
# stages nested inside root / opening / grow (and ``stall`` inside replay)
DEVICE_STAGES = ("select", "partition", "hist", "scan", "stall")
DEVICE_SCOPES = DEVICE_PHASES + DEVICE_STAGES
# inside a phase or stage of the sharded learners (``parallel/``) only: the
# collectives of one exchange site and the slices and converts around them
# (``benchmark/phases_mesh.json`` holds the same; the serial step, every
# scope of which is in DEVICE_SCOPES, has none)
MESH_STAGES = ("exchange",)

# host spans of the training window (recorded as ``lgbt.<name>``).  No site
# records ``tree_dispatch`` since the pipelined path's span became
# ``dispatch`` (PR 28); the name stays while ``benchmark/phases.json``, which
# a test holds equal to this tuple and only a benchmark PR may edit, has it
SPAN_PREFIX = "lgbt."
HOST_SPANS = ("iteration", "bagging", "feature_sample", "dispatch",
              "tree_dispatch", "tree_train", "flush", "d2h_wait",
              "assemble_tree", "gradients", "score_update", "eval_valid")


# the ``name=`` of every ``pl.pallas_call`` in ``ops/`` (a test holds the
# call sites to this tuple): what the Mosaic dump and the ``tf_op`` path
# show, and what ``benchmark/kernels/*.json`` looks for.  No call site is
# named ``apply_partition_permute`` since its kernel went (PR 30); the name
# stays while ``benchmark/phases.json`` has it, as ``tree_dispatch`` above
KERNEL_NAMES = ("build_histogram_pallas", "build_histogram_packed",
                "build_histogram_segments", "build_histogram_multislot",
                "apply_partition_permute", "find_best_splits_batched",
                "fused_child_scans")


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`DEVICE_SCOPES` or
    :data:`MESH_STAGES`."""
    if name not in DEVICE_SCOPES + MESH_STAGES:
        raise ValueError(f"{name!r} is not one of the program's device "
                         f"scopes {DEVICE_SCOPES + MESH_STAGES}")
    return jax.named_scope(name)
