"""In-process worker ring over real loopback TCP: the transport rig of
``test_race.py``'s fixture scripts, ``test_tcp_matrix.py`` and
``test_fault_injection.py``."""

import threading


def ring_harness(p, segment_bytes, stripes, reconnect_budget=None):
    """The exact transport of multi-process tcp mode: one PeerService
    mailbox + RingPlane per rank, control MuxClients + bulk
    StripeClients.  ``reconnect_budget`` arms the self-healing session
    layer explicitly (None = the env default, i.e. off), so a test
    never mutates process env."""
    from horovod_tpu.ops.tcp_dataplane import PeerService, RingPlane
    from horovod_tpu.run.service import network

    key = b"0" * 32
    services = [PeerService(key) for _ in range(p)]

    def resolver(rank):
        return network.MuxClient([("127.0.0.1", services[rank].port)],
                                 key, timeout=60,
                                 reconnect_budget=reconnect_budget)

    def resolve_bulk(rank):
        return network.StripeClient(
            [("127.0.0.1", services[rank].port)], key, timeout=60,
            reconnect_budget=reconnect_budget)

    planes = [RingPlane(r, services[r], resolver, resolve_bulk,
                        segment_bytes=segment_bytes, stripes=stripes)
              for r in range(p)]
    return services, planes


def run_all(planes, fn):
    """``fn(rank)`` on one thread per plane; the first error is raised."""
    errs = []

    def run(r):
        try:
            fn(r)
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    threads = [threading.Thread(target=run, args=(r,))
               for r in range(len(planes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
