"""Worker bootstrap: runs inside every launched worker BEFORE the user
script, so jax.distributed is initialized before any code can touch the
XLA backend (jax requires initialize() first). The reference trainers do
the equivalent inside init_parallel_env from the launcher's env; here the
ordering constraint is hard, so the launcher owns it."""

from __future__ import annotations

import os
import runpy
import sys


def main():
    # first heartbeat BEFORE the heavy imports/rendezvous: the launcher's
    # hang watchdog must not mistake a long jax init for a wedged worker
    from . import heartbeat

    heartbeat.write(step=None)
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    # PADDLE_SKIP_DIST_INIT: launcher-supervised workers that shard only
    # DATA (independent replicas over a sharded stream — no cross-rank
    # collectives, per-rank checkpoints) opt out of the coordination
    # service: they must not share commit barriers that would couple
    # their otherwise-independent checkpoint directories. Supervision
    # (heartbeats, watchdog, restart budget) is unaffected.
    if nprocs > 1 and not os.environ.get("PADDLE_SKIP_DIST_INIT"):
        import jax

        coord = (os.environ.get("PADDLE_MASTER")
                 or os.environ.get("MASTER_ADDR", "127.0.0.1"))
        port = os.environ.get("MASTER_PORT", "8471")
        jax.distributed.initialize(
            coordinator_address=f"{coord}:{port}",
            num_processes=nprocs,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
        )
    from ...jit.cache import place_compile_cache

    place_compile_cache()
    script = sys.argv[1]
    sys.argv = sys.argv[1:]
    runpy.run_path(script, run_name="__main__")


if __name__ == "__main__":
    main()
