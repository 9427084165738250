"""Multi-process DCN worker: one PROCESS of a jax.distributed cluster.

The reference scales across hosts with its cluster messenger over the
network (src/ceph_osd.cc:550-630 boot joining the cluster fabric); the
TPU build's DCN fabric is jax.distributed + XLA collectives.  Every
prior round exercised the ("host","dp","shard") mesh inside ONE
process over virtual devices; this worker is the leg that crosses a
REAL process boundary: N processes (each with its own CPU devices)
join through the gRPC coordination service, build the host mesh whose
"host" axis follows jax.process_index(), and run the full distributed
EC write + recovery step with every verification computed INSIDE the
SPMD program (replicated scalars out — no host-side gathering of
cross-process shards needed).

Launched by tests/test_multiprocess_dcn.py and by
__graft_entry__.dryrun_multichip's multi-process leg:

    python -m ceph_tpu.parallel.dcn_worker \
        --coordinator 127.0.0.1:PORT --num-processes 2 --process-id I
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--coordinator", required=True)
    p.add_argument("--num-processes", type=int, required=True)
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--devices-per-host", type=int, default=4)
    args = p.parse_args()

    # hermetic CPU backend BEFORE any backend init (utils/jaxenv).
    # The flag is forced here even over an
    # inherited XLA_FLAGS: each WORKER process must get exactly
    # devices_per_host devices regardless of the parent's setting.
    flag = "--xla_force_host_platform_device_count"
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(flag)]
    os.environ["XLA_FLAGS"] = " ".join(
        flags + [f"{flag}={args.devices_per_host}"])
    from ceph_tpu.utils.jaxenv import force_cpu
    force_cpu()
    import jax

    from ceph_tpu.parallel.mesh import init_multihost
    joined = init_multihost(args.coordinator, args.num_processes,
                            args.process_id)
    assert joined, "init_multihost declined a multi-process config"
    assert jax.process_count() == args.num_processes, \
        jax.process_count()

    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.models.stripe_codec import StripeCodec
    from ceph_tpu.parallel import DistributedStripeEC, make_host_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_host_mesh()  # host axis == the process boundary
    assert mesh.shape["host"] == args.num_processes
    codec = StripeCodec(k=8, m=3)
    dec = DistributedStripeEC(codec, mesh, batch_axes=("host", "dp"))

    B = 2 * dec.n_dp
    L = 256 * dec.n_shard
    # every process derives the same GLOBAL payload, then materializes
    # only its addressable shards of the distributed array
    data_np = np.random.default_rng(42).integers(
        0, 256, (B, 8, L), dtype=np.uint8)
    sharding = NamedSharding(mesh, P(("host", "dp"), None, None))
    data = jax.make_array_from_callback(
        data_np.shape, sharding, lambda idx: data_np[idx])

    stack, digest = dec.write_step(data)
    # verifications stay inside SPMD; only replicated scalars come out
    sys_err = int(jax.jit(
        lambda s, d: jnp.sum(jnp.bitwise_xor(
            s[:, :8, :], d), dtype=jnp.uint32))(stack, data))
    available = [0, 2, 3, 5, 6, 7, 8, 10]  # lose chunks 1, 4, 9
    rec = dec.recovery_step(available)(stack)
    rec_err = int(jax.jit(
        lambda r, d: jnp.sum(jnp.bitwise_xor(r, d),
                             dtype=jnp.uint32))(rec, data))
    stats = jax.jit(dec.make_stats_step())(stack)
    stats_sum = int(jax.jit(
        lambda s: jnp.sum(s, dtype=jnp.uint64))(stats))

    print(json.dumps({
        "process_id": args.process_id,
        "process_count": jax.process_count(),
        "devices_total": len(jax.devices()),
        "devices_local": len(jax.local_devices()),
        "mesh": dict(mesh.shape),
        "digest": int(np.asarray(digest)),
        "systematic_err": sys_err,
        "recovery_err": rec_err,
        "stats_sum": stats_sum,
    }))
    return 0 if sys_err == 0 and rec_err == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
