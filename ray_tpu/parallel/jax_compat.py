"""Private-surface access for the elastic gang's jax.distributed world.

Written against the one installed stack (jax/jaxlib 0.9.0): the
coordination-service factories live in ``jax._src.lib._jax`` and take a
single ``heartbeat_timeout``.  Nothing here falls back when that
private surface moves — an ImportError/TypeError is the signal to port
this file, and tests/test_elastic_gang.py pins the "resilient" result.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# elastic jax.distributed (parallel/gang.py)
#
# Two capabilities the elastic gang needs that the public
# jax.distributed surface doesn't expose:
#
#   * SURVIVABLE membership: the stock DistributedRuntimeClient's
#     missed-heartbeat/error-poll callback LOG(FATAL)s the process the
#     moment ANY peer dies — the exact opposite of shrink-and-resume.
#     ``distributed_initialize(resilient=True)`` builds the client with a
#     no-op callback and ``shutdown_on_destruction=False`` so member
#     death is an ERROR the gang layer handles, not process suicide.
#     The coordination service must additionally never DECLARE a member
#     dead: this XLA propagates "unhealthy task" findings to every
#     surviving client through error polling, and the agent's polling
#     thread terminates the process (uncatchable std::bad_cast inside
#     the C++->Python callback hop) when it hands the error over — so
#     heartbeat-miss detection is effectively disabled on both sides
#     (``heartbeat_timeout`` ~ 10^7 s) and membership health belongs
#     to the gang layer alone (actor death watch + ping probes; a dead
#     peer still poisons in-flight collectives via gloo's own TCP
#     errors, which surface as ordinary Python exceptions).
#   * ABANDON: ``distributed_abandon()`` force-leaves a (possibly
#     poisoned) world.  It must not attempt ANY shutdown handshake:
#     the collective shutdown barrier can never complete once a peer is
#     dead, and its timeout error would be propagated to the surviving
#     clients' polling threads — the same process-killing path as
#     above.  The old client/service are instead parked in a
#     module-level list (a deliberate, bounded leak: one pair per
#     re-gang) so not even a destructor runs against the old world;
#     the gang then clears jax's cached backends so the next initialize
#     sees the NEW world's global-device view.


def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int, *, resilient: bool = True,
                           heartbeat_timeout_s: int = 10_000_000,
                           init_timeout_s: int = 120) -> str:
    """Initialize jax.distributed; returns "resilient" when the
    peer-death-survivable client was installed, "plain" only when the
    caller asked for the public API (``resilient=False``).  A moved
    private surface raises — elastic shrink must not silently degrade
    to a client that kills surviving members."""
    import jax
    if not resilient:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        return "plain"
    from jax._src import distributed
    from jax._src.lib import _jax
    st = distributed.global_state
    if st.client is not None:
        raise RuntimeError("jax.distributed already initialized")
    port = coordinator_address.rsplit(":", 1)[1]
    if process_id == 0:
        st.service = _jax.get_distributed_runtime_service(
            "[::]:" + port, num_processes,
            heartbeat_timeout=heartbeat_timeout_s)
    client = _jax.get_distributed_runtime_client(
        coordinator_address, process_id,
        init_timeout=init_timeout_s, shutdown_timeout=5,
        heartbeat_timeout=heartbeat_timeout_s,
        missed_heartbeat_callback=lambda *a, **k: None,
        shutdown_on_destruction=False, use_compression=True)
    client.connect()
    st.client = client
    st.process_id = process_id
    st.num_processes = num_processes
    st.coordinator_address = coordinator_address
    return "resilient"


# worlds left behind by distributed_abandon().  Holding the references
# forever is the point: calling .shutdown() on either object — or even
# letting its destructor run — talks to a world with a dead member, and
# the resulting barrier-timeout error comes back through the surviving
# clients' error-polling threads as process termination (see the module
# comment above).  One (client, service) pair leaks per re-gang; the
# old client keeps heartbeating the old service quietly, generating no
# errors, until the process exits.
_abandoned_worlds: list = []


def distributed_abandon(timeout_s: float = 20.0) -> None:
    """Leave the current jax.distributed world WITHOUT any shutdown
    handshake: the collective shutdown barrier can never complete once
    a peer is dead, and even ATTEMPTING it propagates a timeout error
    that kills the surviving peers' polling threads.  The old
    client/service pair is parked (never shut down, never destroyed) so
    the old world stays silent; the global_state slots are cleared so
    the next distributed_initialize builds a fresh world."""
    from jax._src import distributed
    st = distributed.global_state
    if st.client is not None or st.service is not None:
        _abandoned_worlds.append((st.client, st.service))
    st.client = None
    st.service = None
    st.preemption_sync_manager = None
    st.process_id = None
    st.num_processes = None
    st.coordinator_address = None
