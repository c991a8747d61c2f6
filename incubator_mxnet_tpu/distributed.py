"""Multi-host bootstrap (the rebuild of the reference's launcher + ps-lite
topology plumbing: tools/launch.py, dmlc tracker env, kvstore rank/size).

The reference starts schedulers/servers/workers over ssh and wires them
with DMLC_* env vars. TPU-native: every host runs the SAME SPMD program;
`jax.distributed.initialize` forms the cluster (coordinator + N processes),
after which `jax.devices()` spans all hosts and one `Mesh` over it gives
collectives that ride ICI within a pod slice and DCN across slices. KVStore
`rank`/`num_workers` and `dist_*` modes read this state.

Usage (one command per host, reference-launcher style):
    import incubator_mxnet_tpu as mx
    mx.distributed.init(coordinator_address="host0:1234",
                        num_processes=4, process_id=HOST_ID)
    mesh = mx.distributed.global_mesh({"dp": -1})
    # ... FusedTrainStep(net, loss, opt, mesh=mesh) as single-host ...

On TPU pods with the standard runtime, `init()` with no arguments
auto-discovers everything from the pod metadata (jax's default).
"""
from __future__ import annotations

import jax

__all__ = ["init", "shutdown", "rank", "num_workers", "local_devices",
           "global_devices", "global_mesh", "barrier", "is_initialized"]

_state = {"initialized": False}


def init(coordinator_address=None, num_processes=None, process_id=None,
         local_device_ids=None, initialization_timeout=None):
    """Form the multi-host cluster (parity: the reference launcher's
    scheduler rendezvous). No-op when already initialized or single-host
    with no coordinator given.

    Arguments default from the MXTPU_COORDINATOR / MXTPU_NUM_PROCESSES /
    MXTPU_PROCESS_ID environment (set by tools/launch.py, the analogue of
    the reference launcher's DMLC_* variables), so an unmodified training
    script that calls ``mx.distributed.init()`` works under the
    launcher.

    ``initialization_timeout`` (seconds; env MXTPU_INIT_TIMEOUT) bounds
    the rendezvous wait — widen it on loaded machines where sibling
    processes start staggered (CI under full-suite load), shrink it in
    fail-fast launchers."""
    if _state["initialized"]:
        return
    import os
    if (coordinator_address is None and num_processes is None
            and process_id is None):
        # env applies only as a COMPLETE set — a partial/leaked variable
        # (e.g. a stray MXTPU_NUM_PROCESSES) must not reroute a plain
        # single-host init() into a hard-crashing explicit rendezvous
        from .settings import env_str
        env_vals = [env_str("MXTPU_COORDINATOR", ""),
                    env_str("MXTPU_NUM_PROCESSES", ""),
                    env_str("MXTPU_PROCESS_ID", "")]
        if all(env_vals):
            coordinator_address = env_vals[0]
            num_processes = int(env_vals[1])
            process_id = int(env_vals[2])
    if initialization_timeout is None:
        from .settings import env_int
        initialization_timeout = env_int("MXTPU_INIT_TIMEOUT", None)
    timeout_kw = ({} if initialization_timeout is None
                  else {"initialization_timeout": int(initialization_timeout)})
    if coordinator_address is not None:
        # Cross-process computations on the CPU backend (loopback test
        # clusters, CPU fleets) need a collectives implementation; jax
        # does not default one on this version, and without it every
        # process_allgather dies with "Multiprocess computations aren't
        # implemented on the CPU backend". Must be set BEFORE the first
        # backend materialization; harmless for TPU (per-backend knob).
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:  # noqa: BLE001 — older/newer jax: keep going
            pass
    if coordinator_address is None and num_processes is None:
        # single-host or TPU-pod auto-discovery; jax treats absent args as
        # "use the runtime's own metadata" and works standalone too
        try:
            jax.distributed.initialize(**timeout_kw)
        except Exception as e:  # noqa: BLE001
            # plain single-process runs land here by design; on a real pod
            # a swallowed rendezvous error would strand the OTHER hosts in
            # initialize() — so always leave a trace of why we degraded
            import logging
            logging.getLogger(__name__).warning(
                "distributed.init auto-discovery failed (%r); continuing "
                "single-process — if this host is part of a pod, pass "
                "coordinator_address/num_processes/process_id explicitly",
                e)
            return
    else:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
            **timeout_kw)
    _state["initialized"] = True


def shutdown():
    if _state["initialized"]:
        # stop any dist_async server threads FIRST: a grpc poll in flight
        # while the coordination client is destroyed aborts the process
        # (C++ exception in a detached thread)
        from .kvstore import async_ps
        async_ps.stop_all()
        jax.distributed.shutdown()
        _state["initialized"] = False


def is_initialized() -> bool:
    return _state["initialized"]


def rank() -> int:
    """This process's index (parity: kv.rank / DMLC_RANK)."""
    return jax.process_index()


def num_workers() -> int:
    """Total processes (parity: kv.num_workers / DMLC_NUM_WORKER)."""
    return jax.process_count()


def local_devices():
    return jax.local_devices()


def global_devices():
    return jax.devices()


def global_mesh(axes=None):
    """Mesh over ALL hosts' devices (ICI inside a slice, DCN across) —
    the multi-host analogue of make_mesh. Put the fastest-varying axis
    (tp/sp) innermost so its collectives stay on ICI."""
    from .parallel import make_mesh
    return make_mesh(axes or {"dp": -1}, devices=jax.devices())


def barrier(name="mxtpu_barrier"):
    """Block until every process reaches this point (parity: kv.barrier
    across workers)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)
