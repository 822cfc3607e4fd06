"""Subprocess entry point for TpuDistributor local spawn.

Reads TPUDL_* env (coordinator, process count/id; the distributor also
sets JAX_PLATFORMS for it), brings up jax.distributed against the
coordinator, runs the pickled payload, and
writes ("ok", result) or ("error", traceback) to the result path.
"""

import os
import pickle
import sys
import traceback

from tpudl.analysis.registry import env_require, env_str


def main() -> int:
    payload_path, result_path = sys.argv[1], sys.argv[2]
    from tpudl.analysis.registry import env_int

    coord = env_require("TPUDL_COORDINATOR")
    nproc = env_int("TPUDL_NUM_PROCESSES", required=True)
    pid = env_int("TPUDL_PROCESS_ID", required=True)

    import jax

    jax.distributed.initialize(coord, num_processes=nproc, process_id=pid)

    # Observability: the distributor points TPUDL_OBS_DIR at its
    # workers/ merge directory; enable eagerly (rather than waiting for
    # fit()'s lazy activation) so every worker leaves a span file with a
    # top-level worker_run span even when the payload touches no
    # instrumented layer — per-rank wall-clock is what the straggler
    # report attributes.
    rec = None
    obs_dir = env_str("TPUDL_OBS_DIR")
    if obs_dir:
        from tpudl.obs import spans as obs_spans

        rec = obs_spans.enable(obs_dir, process=pid)

    t0 = rec.clock() if rec is not None else 0.0
    try:
        with open(payload_path, "rb") as f:
            fn, args, kwargs = pickle.load(f)
        result = ("ok", fn(*args, **kwargs))
        code = 0
    except Exception:
        result = ("error", traceback.format_exc())
        code = 1
    if rec is not None:
        rec.record(
            "worker_run", "worker", t0, rec.clock() - t0,
            {"ok": code == 0, "platform": jax.default_backend()},
        )

    tmp = result_path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(result, f)
    os.replace(tmp, result_path)

    jax.distributed.shutdown()
    return code


if __name__ == "__main__":
    sys.exit(main())
