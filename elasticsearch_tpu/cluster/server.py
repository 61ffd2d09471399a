"""Multi-process cluster node over real TCP + a synchronous client.

Runs the SAME ClusterNode (coordination, replication, recovery, search
scatter/gather) that the deterministic simulation tests exercise, but over
`transport/tcp.py` sockets — the deployment shape of the reference
(bin/elasticsearch → Node.start → TransportService on 9300;
node/Node.java:279,314).

As a module:  python -m elasticsearch_tpu.cluster.server \
                  --node-id n1 --port 9301 \
                  --peers n1=127.0.0.1:9301,n2=127.0.0.1:9302,n3=127.0.0.1:9303

In-process:   NodeServer(...) — used by tests to boot a real-socket
              cluster inside one process (threads instead of processes).

Client actions (served on every node, coordinator-style):
  client:status, client:create_index, client:bulk, client:get,
  client:search — the transport-level analog of the REST surface for
  cluster deployments; `TcpClient` wraps them synchronously.
"""

from __future__ import annotations

import threading

from ..transport.base import TransportService
from ..transport.tcp import TcpTransportNetwork
from .node import ClusterNode


class NodeServer:
    def __init__(self, node_id: str, voting_nodes: list[str],
                 peers: dict[str, tuple[str, int]],
                 host: str = "127.0.0.1", port: int = 0):
        self.network = TcpTransportNetwork(node_id, host, port)
        for n, (h, p) in peers.items():
            if n != node_id:
                self.network.add_peer(n, h, p)
        self.node = ClusterNode(node_id, voting_nodes, self.network)
        svc = self.node.service
        svc.register_async_handler("client:status", self._on_status)
        svc.register_async_handler("client:create_index", self._on_create_index)
        svc.register_async_handler("client:bulk", self._on_bulk)
        svc.register_async_handler("client:get", self._on_get)
        svc.register_async_handler("client:search", self._on_search)

    @property
    def port(self) -> int:
        return self.network.port

    def start(self):
        # all cluster work runs on the network's dispatch thread
        self.network.submit(self.node.start)

    def close(self):
        self.network.close()

    # -- client actions (already on the dispatch thread) -------------------

    def _on_status(self, req, from_node, channel):
        st = self.node.state
        started = sum(
            1
            for shards in st.routing.values()
            for assigns in shards.values()
            for a in assigns
            if a["state"] == "STARTED"
        )
        channel.send_response({
            "node": self.node.node_id,
            "mode": self.node.coordinator.mode,
            "leader": self.node.coordinator.leader,
            "term": st.term,
            "version": st.version,
            "nodes": sorted(st.nodes),
            "indices": sorted(st.indices),
            "started_shards": started,
        })

    def _on_create_index(self, req, from_node, channel):
        self.node.create_index(req["index"], req.get("mappings"),
                               req.get("settings"), channel.send_response)

    def _on_bulk(self, req, from_node, channel):
        ops = [tuple(op) for op in req["ops"]]
        self.node.client_bulk(req["index"], ops, channel.send_response)

    def _on_get(self, req, from_node, channel):
        self.node.client_get(req["index"], req["id"], channel.send_response)

    def _on_search(self, req, from_node, channel):
        self.node.client_search(req["index"], req.get("body") or {},
                                channel.send_response,
                                size=req.get("size", 10))


class TcpClient:
    """Synchronous transport client for driving a TCP cluster (tests,
    demos, CLI tooling) — the analog of the low-level Java transport
    client."""

    def __init__(self, client_id: str | None = None):
        if client_id is None:
            import uuid

            # unique by default: response routing on the server is keyed by
            # (sender id, request id), so two clients must not share an id
            client_id = f"_client-{uuid.uuid4().hex[:8]}"
        self.network = TcpTransportNetwork(client_id)
        self.service = TransportService(client_id, self.network)

    def add_node(self, node_id: str, host: str, port: int):
        self.network.add_peer(node_id, host, port)

    def request(self, node_id: str, action: str, body: dict,
                timeout: float = 15.0) -> dict:
        done = threading.Event()
        out: dict = {}

        def ok(resp):
            out["resp"] = resp
            done.set()

        def fail(err):
            out["err"] = err
            done.set()

        self.network.submit(lambda: self.service.send_request(
            node_id, action, body, ok, fail, timeout=timeout))
        if not done.wait(timeout + 5.0):
            raise TimeoutError(f"[{action}] to [{node_id}] hung")
        if "err" in out:
            raise out["err"]
        return out["resp"]

    def wait_for(self, predicate, nodes, timeout: float = 30.0,
                 action: str = "client:status", body: dict | None = None):
        """Poll every node's status until predicate(statuses) is true."""
        import time

        deadline = time.monotonic() + timeout
        last = None
        while time.monotonic() < deadline:
            try:
                last = [self.request(n, action, body or {}, timeout=3.0)
                        for n in nodes]
                if predicate(last):
                    return last
            except Exception:  # noqa: BLE001 - node still starting
                pass
            time.sleep(0.1)
        raise TimeoutError(f"cluster condition not reached; last={last}")

    def close(self):
        self.network.close()


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="elasticsearch_tpu cluster node")
    ap.add_argument("--node-id", required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--peers", required=True,
                    help="n1=host:port,n2=host:port,... (voting config)")
    ap.add_argument("--http-port", type=int, default=None,
                    help="serve the cluster REST gateway on this port "
                         "(every node answers every data-plane API)")
    args = ap.parse_args(argv)

    peers: dict[str, tuple[str, int]] = {}
    for part in args.peers.split(","):
        nid, _, addr = part.partition("=")
        h, _, p = addr.partition(":")
        peers[nid] = (h, int(p))
    server = NodeServer(args.node_id, sorted(peers), peers,
                        host=args.host, port=args.port)
    server.start()
    gateway = None
    if args.http_port is not None:
        from .http import HttpGateway

        gateway = HttpGateway(server, host=args.host,
                              port=args.http_port).start()
    print(f"node [{args.node_id}] listening on {args.host}:{server.port}"
          + (f", http {gateway.port}" if gateway else ""),
          flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        if gateway:
            gateway.close()
        server.close()


if __name__ == "__main__":
    main()
