"""openr-tpu daemon entry point.

The analogue of the reference's ``openr/Main.cpp`` main(): parse config
(JSON file via --config, or legacy flags), assemble the module graph,
start the ctrl server and watchdog, run until SIGINT/SIGTERM, tear down
in reverse order.

Run:  python -m openr_tpu.main --config node.json
      python -m openr_tpu.main --node-name fc001 --ifaces eth0,eth1
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading

from openr_tpu.config.config import OpenrConfig
from openr_tpu.daemon import OpenrNode
from openr_tpu.monitor.watchdog import Watchdog
from openr_tpu.spark.io_provider import UdpIoProvider


def _is_legacy_invocation(argv) -> bool:
    """A reference-style gflags invocation is detected by any
    underscore-named flag from the translated gflag subset
    (``--node_name=...``). The native argparse surface uses dashes, so
    the two dialects never overlap on a single argument."""
    from openr_tpu.config.gflags import GFLAG_DEFS

    for arg in argv:
        if not arg.startswith("--"):
            continue
        name = arg[2:].partition("=")[0]
        if "_" not in name:
            continue
        if name in GFLAG_DEFS or (
            name.startswith("no") and name[2:] in GFLAG_DEFS
        ):
            return True
    return False


def parse_args(argv):
    parser = _build_parser()
    if _is_legacy_invocation(argv):
        # the WHOLE argv goes through the gflag shim: mixing it into
        # argparse would silently strip flags the two surfaces share
        # (--areas, --dryrun, --config). Parsing an empty argv gives the
        # native defaults, so both paths share one attribute contract.
        args = parser.parse_args([])
        args.legacy_argv = list(argv)
        return args
    # strict parse: unknown/typo'd flags must fail fast
    args = parser.parse_args(argv)
    args.legacy_argv = None
    return args


def _build_parser():
    parser = argparse.ArgumentParser(prog="openr-tpu")
    parser.add_argument("--config", help="JSON config file")
    # legacy flag surface (reference: 99 gflags in common/Flags.cpp;
    # the load-bearing subset)
    parser.add_argument("--node-name", default=None)
    parser.add_argument("--areas", default="0")
    parser.add_argument("--ifaces", default="", help="comma separated")
    parser.add_argument("--ctrl-port", type=int, default=2018)
    parser.add_argument("--dryrun", action="store_true")
    parser.add_argument("--enable-v4", action="store_true")
    parser.add_argument("--use-rtt-metric", action="store_true")
    parser.add_argument("--solver-backend", default="device",
                        choices=["device", "host"])
    parser.add_argument(
        "--enable-netlink-fib", action="store_true",
        help="program routes into the kernel via an in-process "
             "NetlinkFibHandler over rtnetlink (reference: "
             "Main.cpp:343-361)",
    )
    parser.add_argument(
        "--fib-agent-port", type=int, default=0,
        help="connect to an out-of-process platform agent "
             "(python -m openr_tpu.platform.agent) instead",
    )
    parser.add_argument(
        "--fib-agent-thrift", action="store_true",
        help="the platform agent speaks the reference FibService "
             "thrift wire (e.g. an FBOSS-style switch agent, or "
             "openr_tpu.platform.agent --thrift)",
    )
    parser.add_argument(
        "--spark-port", type=int, default=None,
        help="UDP multicast port (default: config spark.mcast_port)",
    )
    parser.add_argument(
        "--tls-cert", default=None,
        help="serve the ctrl API over TLS with this PEM cert chain "
             "(reference: the thrift ctrl server's optional TLS; the "
             "breeze client auto-falls-back secure -> plain)",
    )
    parser.add_argument(
        "--tls-key", default=None,
        help="PEM private key for --tls-cert",
    )
    parser.add_argument("-v", "--verbose", action="count", default=0)
    return parser


def build_config(args) -> OpenrConfig:
    if getattr(args, "legacy_argv", None) is not None:
        # reference-style gflags invocation (--node_name=... etc.):
        # translate through the shim (reference: config/GflagConfig.h)
        from openr_tpu.config.gflags import load_config_from_argv

        return load_config_from_argv(args.legacy_argv)
    if args.config:
        return OpenrConfig.from_file(args.config)
    if not args.node_name:
        raise SystemExit("either --config or --node-name is required")
    from openr_tpu.config.config import AreaConfig, LinkMonitorConfig

    return OpenrConfig(
        node_name=args.node_name,
        areas=[AreaConfig(area_id=a) for a in args.areas.split(",")],
        openr_ctrl_port=args.ctrl_port,
        dryrun=args.dryrun,
        enable_v4=args.enable_v4,
        link_monitor=LinkMonitorConfig(use_rtt_metric=args.use_rtt_metric),
        solver_backend=args.solver_backend,
    )


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname).1s %(name)s: %(message)s",
    )
    config = build_config(args)
    log = logging.getLogger("openr_tpu.main")
    log.info("starting openr-tpu node %s", config.node_name)

    # persistent XLA compilation cache: a restarted daemon loads every
    # already-seen kernel instead of compiling it again
    from openr_tpu.utils import compile_cache

    try:
        log.info("compile cache at %s", compile_cache.enable())
    except OSError as exc:
        # a read-only install: set JAX_COMPILATION_CACHE_DIR to a
        # writable directory to get warm restarts
        log.warning("compile cache disabled: %s", exc)

    if config.enable_solver_mesh:
        # process-global: every KSP2 engine this daemon builds shards
        # its resident all-pairs state over the local device mesh
        import jax

        from openr_tpu.decision import ksp2_engine
        from openr_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(jax.devices())
        ksp2_engine.set_engine_mesh(mesh)
        log.info(
            "solver mesh enabled: %d device(s), KSP2 engine bound %d",
            mesh.devices.size, ksp2_engine.engine_max_nodes(),
        )

    from openr_tpu.config_store.persistent_store import PersistentStore

    config_store = PersistentStore(config.persistent_store_path)
    spark_port = args.spark_port or config.spark.mcast_port
    io_provider = UdpIoProvider(port=spark_port)
    area = config.areas[0].area_id

    fib_agent_port = args.fib_agent_port
    enable_netlink_fib = (
        args.enable_netlink_fib or config.enable_netlink_fib_handler
    )
    if fib_agent_port and enable_netlink_fib:
        raise SystemExit(
            "--fib-agent-port and --enable-netlink-fib are mutually "
            "exclusive: the agent owns the kernel boundary"
        )
    if args.fib_agent_thrift and not fib_agent_port:
        raise SystemExit(
            "--fib-agent-thrift requires --fib-agent-port (otherwise "
            "the no-op mock agent would silently swallow every route)"
        )
    # pure argument validation: a bad cert invocation must die BEFORE
    # the daemon starts announcing itself, not flap neighbors after
    ssl_context = None
    if bool(args.tls_cert) != bool(args.tls_key):
        raise SystemExit("--tls-cert and --tls-key go together")
    if args.tls_cert:
        import ssl

        ssl_context = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        try:
            ssl_context.load_cert_chain(args.tls_cert, args.tls_key)
        except (OSError, ssl.SSLError) as exc:
            raise SystemExit(f"--tls-cert/--tls-key: {exc}")
    fib_agent = None  # MockFibAgent default
    if fib_agent_port:
        if args.fib_agent_thrift:
            from openr_tpu.platform.thrift_fib import ThriftFibAgent

            fib_agent = ThriftFibAgent("127.0.0.1", fib_agent_port)
        else:
            from openr_tpu.platform.netlink_fib_handler import TcpFibAgent

            fib_agent = TcpFibAgent("127.0.0.1", fib_agent_port)
        log.info(
            "using platform agent on port %d (%s wire)",
            fib_agent_port,
            "thrift-compact" if args.fib_agent_thrift
            else "framework-rpc",
        )
    elif enable_netlink_fib:
        from openr_tpu.platform.netlink_fib_handler import NetlinkFibHandler
        from openr_tpu.platform.netlink_linux import (
            LinuxNetlinkProtocolSocket,
        )

        # an explicitly requested kernel FIB must not silently degrade
        # to the in-memory mock
        if not LinuxNetlinkProtocolSocket.is_admin_available():
            raise SystemExit(
                "--enable-netlink-fib requires rtnetlink access "
                "(CAP_NET_ADMIN); use --mock on the standalone agent "
                "for simulation"
            )
        fib_agent = NetlinkFibHandler(LinuxNetlinkProtocolSocket())
        log.info("in-process netlink FIB handler (rtnetlink)")

    # loopback address programming for the prefix allocator needs its own
    # netlink socket (the FIB handler owns route programming only)
    alloc_netlink = None
    if config.prefix_alloc.enabled and config.prefix_alloc.set_loopback_addr:
        from openr_tpu.platform.netlink_linux import (
            LinuxNetlinkProtocolSocket as _NlSock,
        )

        if _NlSock.is_admin_available():
            alloc_netlink = _NlSock()
        else:
            log.warning(
                "set_loopback_address requested but rtnetlink is not "
                "available (needs CAP_NET_ADMIN): the elected prefix "
                "will be advertised but NOT programmed on %s",
                config.prefix_alloc.loopback_iface,
            )

    # resolve tracked interfaces (and their areas) up front
    ifaces = [i for i in args.ifaces.split(",") if i]
    if not ifaces and args.legacy_argv is not None:
        # reference semantics: interfaces come from the system, filtered
        # by the configured area regexes (iface_regex_include/exclude) —
        # without this a gflags-started daemon would track nothing and
        # never form an adjacency
        import socket as _socket

        ifaces = [
            name
            for _, name in _socket.if_nameindex()
            if name != "lo"
            and any(a.matches_interface(name) for a in config.areas)
        ]
    interface_areas = {}
    for if_name in ifaces:
        for a in config.areas:
            if a.matches_interface(if_name):
                interface_areas[if_name] = a.area_id
                break

    # cross-process KvStore peering: neighbors advertise their peer
    # port in Spark handshakes (Spark.thrift:97 kvStoreCmdPort) and we
    # dial their link-local transport address. Wire selected by
    # kvstore.enable_kvstore_thrift (framed CompactProtocol interop vs
    # the framework RPC codec).
    def peer_transport_factory(nbr):
        if nbr.kvstore_peer_port <= 0:
            return None
        host = None
        if nbr.transport_address_v6.addr:
            host = nbr.transport_address_v6.to_str()
            if host.startswith("fe80"):
                host = f"{host}%{nbr.local_if_name}"
        elif nbr.transport_address_v4.addr:
            host = nbr.transport_address_v4.to_str()
        if not host:
            return None
        if config.kvstore.enable_kvstore_thrift:
            from openr_tpu.kvstore.thrift_peer import ThriftPeerTransport

            return ThriftPeerTransport(host, nbr.kvstore_peer_port)
        from openr_tpu.kvstore.transport import TcpPeerTransport

        return TcpPeerTransport(host, nbr.kvstore_peer_port)

    node = OpenrNode(
        config.node_name,
        io_provider,
        fib_agent=fib_agent,
        peer_transport_factory=peer_transport_factory,
        area=area,
        areas=config.area_ids(),
        interface_areas=interface_areas or None,
        spark_config=dict(
            hello_interval_s=config.spark.hello_time_s,
            fast_hello_interval_s=config.spark.fastinit_hello_time_ms / 1000,
            handshake_interval_s=config.spark.handshake_time_ms / 1000,
            heartbeat_interval_s=config.spark.keepalive_time_s,
            hold_time_s=config.spark.hold_time_s,
            graceful_restart_time_s=config.spark.graceful_restart_time_s,
            wire_format=config.spark.wire_format,
            domain=config.domain,
        ),
        use_rtt_metric=config.link_monitor.use_rtt_metric,
        config_store=config_store,
        solver_backend=config.solver_backend,
        enable_rib_policy=config.enable_rib_policy,
        enable_v4=config.enable_v4,
        enable_lfa=config.enable_lfa,
        enable_ordered_fib=config.enable_ordered_fib_programming,
        enable_bgp_route_programming=(
            config.decision.enable_bgp_route_programming
        ),
        enable_best_route_selection=config.enable_best_route_selection,
        enable_segment_routing=config.enable_segment_routing,
        node_label=config.node_label,
        debounce_min_s=config.decision.debounce_min_ms / 1000,
        debounce_max_s=config.decision.debounce_max_ms / 1000,
        enable_flood_optimization=config.kvstore.enable_flood_optimization,
        is_flood_root=config.kvstore.is_flood_root,
        flood_rate=config.kvstore.flood_rate(),
        per_prefix_keys=config.per_prefix_keys,
        prefix_alloc=config.prefix_alloc,
        netlink=alloc_netlink,
    )
    node.ctrl_handler._config = config

    watchdog = None
    if config.enable_watchdog:
        watchdog = Watchdog(
            interval_s=config.watchdog.interval_s,
            thread_timeout_s=config.watchdog.thread_timeout_s,
            max_memory_bytes=config.watchdog.max_memory_mb * 1024 * 1024,
        )
        for name, evb in (
            ("kvstore", node.kvstore.evb),
            ("decision", node.decision.evb),
            ("fib", node.fib.evb),
            ("spark", node.spark.evb),
            ("linkmonitor", node.link_monitor.evb),
            ("prefixmgr", node.prefix_manager.evb),
            ("monitor", node.monitor.evb),
        ):
            watchdog.add_evb(name, evb)

    # reference: Main.cpp:595-601 invokes pluginStart when BGP peering
    # is enabled — here the plugin hook is generic (daemon starts any
    # registered plugin, handing it config.bgp_config), so the gate's
    # counterpart is surfacing a peering section nobody will speak
    if config.is_bgp_peering_enabled():
        from openr_tpu import plugin

        if not plugin.has_plugin():
            log.warning(
                "bgp_config present (%d peers) but no plugin is "
                "registered to speak BGP — peering will not come up",
                len(config.bgp_config.peers),
            )

    # KvStore peer server: what neighbors dial for full-sync and flood
    # (reference: the thrift KvStoreService / legacy zmq ROUTER on port
    # 60002, Constants.h:257). The SERVER always dual-stacks — both
    # wires on the one advertised port, sniffed per connection — so
    # mixed deployments mid-migration sync regardless of which wire
    # each neighbor dials (the reference's dual-transport pattern,
    # KvStore.cpp:2940-2973). enable_kvstore_thrift selects only the
    # wire THIS daemon dials outward. Bound before Spark starts so the
    # handshake advertises a live port.
    from openr_tpu.kvstore.dualstack import DualStackPeerServer

    peer_server = DualStackPeerServer(
        node.kvstore, host="::", port=config.kvstore.peer_port
    )
    peer_server.start()
    node.spark.set_kvstore_peer_port(peer_server.port)
    log.info(
        "kvstore peer server (dual-stack; dialing %s) on port %d",
        "thrift-compact" if config.kvstore.enable_kvstore_thrift
        else "framework-rpc",
        peer_server.port,
    )

    node.start()
    if watchdog is not None:
        watchdog.start()
    port = node.start_ctrl_server(
        port=config.openr_ctrl_port, ssl_context=ssl_context
    )
    log.info(
        "ctrl server listening on port %d%s",
        port,
        " (TLS)" if ssl_context is not None else "",
    )

    for if_name in ifaces:
        node.add_interface(if_name)
        log.info(
            "tracking interface %s (area %s)",
            if_name,
            interface_areas.get(if_name, area),
        )

    stop_event = threading.Event()

    def on_signal(signum, frame):
        log.info("signal %d: shutting down", signum)
        stop_event.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    stop_event.wait()

    if watchdog is not None:
        watchdog.stop()
    peer_server.stop()
    node.stop()
    config_store.stop()
    log.info("shutdown complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
