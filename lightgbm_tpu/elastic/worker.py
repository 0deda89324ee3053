"""One membership epoch's training process (subprocess re-entry).

``python -m lightgbm_tpu.elastic.worker <spec.json>`` — launched by the
per-host controller once per epoch, because a jax.distributed cluster can
neither re-initialize nor shrink in place (the coordination service
propagates fatal errors to survivors once a peer dies).  The platform
environment (JAX_PLATFORMS / XLA_FLAGS) must be composed by the
controller into the child env: importing this module already imports jax
via the package.

The worker derives its per-epoch world from the membership record — a
fresh coordinator (``port_base + epoch``), ``num_hosts`` = survivor
count, ``process_id`` = this host's index in the member list — trains to
the ORIGINAL round target with ``resume=true`` (the snapshot dir is
per-HOST, stable across epochs), and exits:

  * 0 — trained to the target; model + result JSON written;
  * ``EXIT_RESHAPE`` — a peer died (``RankDeathError``): next epoch's
    membership was negotiated over the old KV store and written to the
    verdict file for the controller;
  * ``EXIT_DECLARED_DEAD`` — negotiation declared THIS host dead (it
    stalled past the ack deadline);
  * ``EXIT_CONTROL_LOST`` — the anchor/coordination service is gone;
    terminal.

Exits go through ``os._exit``: the normal interpreter shutdown runs
jax.distributed's atexit barrier, which aborts against a dead peer — the
same reason the chaos drills exit this way.
"""

from __future__ import annotations

import json
import os
import sys
import time

from .controller import (EXIT_CONTROL_LOST, EXIT_DECLARED_DEAD,
                         EXIT_RESHAPE, write_json)
from .epoch import MembershipEpoch, confirm_record, negotiate_next_epoch


def _quiesce(epoch: MembershipEpoch, host: int, spec: dict) -> None:
    """Leader-LAST exit ordering for the success path.  The epoch's
    coordination service lives inside rank 0's process; if rank 0 exits
    while a peer is still saving its model, the peer's error-poller
    aborts it (SIGABRT) even though training succeeded.  So rank 0
    lingers until every peer's result file is durable and is the last
    one out.  The wait reads the FILESYSTEM, not the KV store — KV reads
    against the in-process service can crash it natively while peers
    disconnect (the controller's dirty-exit tolerance exists for exactly
    that) — so on a real pod with per-host workdirs this degrades to a
    bounded grace period instead of a handshake."""
    # rank 0 hosts the coordination service — it alone must linger
    # (vetted via the LGB008 allowlist)
    if epoch.rank_of(host) != 0:
        return
    try:
        edir = os.path.dirname(os.path.abspath(spec["result_path"]))
        hosts_root = os.path.dirname(os.path.dirname(edir))
        peers = [os.path.join(hosts_root, f"h{int(h)}",
                              os.path.basename(edir), "result.json")
                 for h in epoch.members if int(h) != int(host)]
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all(os.path.exists(p) for p in peers):
                break
            time.sleep(0.05)
    except Exception:
        pass


def _recover(spec: dict, epoch: MembershipEpoch, host: int, err) -> None:
    """Negotiate the next membership over the dying epoch's KV store,
    write the verdict for the controller, and exit."""
    t0 = time.monotonic()
    try:
        record = negotiate_next_epoch(
            epoch, host, err.dead_ranks,
            deadline_s=float(spec.get("negotiate_deadline_s", 20.0)))
    except ConnectionError as e:
        write_json(spec["verdict_path"], {
            "kind": "control_plane_lost", "failed_epoch": epoch.epoch,
            "error": str(e)})
        os._exit(EXIT_CONTROL_LOST)
    write_json(spec["verdict_path"], {
        "kind": "reshape", "failed_epoch": epoch.epoch,
        "dead_ranks": [int(r) for r in err.dead_ranks],
        "error": str(err), "next": record.to_dict(),
        "negotiate_s": time.monotonic() - t0})
    # verdict is durable — NOW release the anchor (its exit kills the
    # coordination service, and the fatal-error poller takes any process
    # still running down with it, so nothing below this line may matter)
    if epoch.rank_of(host) != 0:
        try:
            confirm_record(record, host)
        except Exception:
            pass
    if int(host) not in record.members:
        os._exit(EXIT_DECLARED_DEAD)
    os._exit(EXIT_RESHAPE)


def main(argv) -> None:
    with open(argv[1]) as fh:
        spec = json.load(fh)
    epoch = MembershipEpoch.from_dict(spec["membership"])
    host = int(spec["host_id"])
    rank = epoch.rank_of(host)

    import jax
    if spec.get("enable_x64"):
        jax.config.update("jax_enable_x64", True)

    import lightgbm_tpu as lgb
    from ..parallel.multihost import RankDeathError

    params = dict(spec["params"])
    params.update({
        "coordinator_address": epoch.coordinator,
        "num_hosts": len(epoch.members),
        "process_id": rank,
        "elastic": True,
        "elastic_epoch": int(epoch.epoch),
        "two_round": True,
        "resume": True,
        "output_model": spec["output_model"],
    })
    params.setdefault("snapshot_freq", 1)

    try:
        dtrain = lgb.Dataset(spec["data"], params=params)
        bst = lgb.train(params, dtrain,
                        num_boost_round=int(spec["num_boost_round"]))
        bst.save_model(spec["output_model"])
        result = {"ok": True, "epoch": int(epoch.epoch), "rank": rank,
                  "members": list(epoch.members),
                  "iterations": int(bst.current_iteration),
                  "model": spec["output_model"]}
        if params.get("telemetry"):
            result["report"] = bst.get_telemetry()
        write_json(spec["result_path"], result)
        _quiesce(epoch, host, spec)
    except RankDeathError as e:
        _recover(spec, epoch, host, e)  # never returns
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv)
