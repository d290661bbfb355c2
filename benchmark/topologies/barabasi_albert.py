"""Topology ``barabasi_albert``: preferential attachment with ``m`` links
per new node, drawn by the repo's native C++ builder with ``graph_seed``."""


def csr(config: dict):
    from p2p_gossip_tpu.runtime import native

    return native.native_barabasi_albert(
        int(config["nodes"]), int(config["m"]), int(config["graph_seed"]))
