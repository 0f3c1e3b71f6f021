"""ClusterBuilder — compiles a specification into a deployed application.

This is the paper's central artifact: the builder consumes a
:class:`~repro.core.dsl.ClusterSpec` (or a bare SPMD step function plus typed
channels) and produces *everything else* with no user intervention:

* the **deployment plan** — the Host-Node-Loader / Node-Loader bootstrap
  of paper §4 and Figure 1 (load network on port 2000/channel 1, application
  network on a separate port, input-end-before-output-end ordering, sync
  barriers, timing return);
* the **wired process network** — for emit/cluster/collect applications, a
  runnable network (``runtime.local``) whose topology is exactly Figure 2 and
  whose protocol is the one model-checked by ``core.verify``;
* the **compiled SPMD step** — for cluster stages that are JAX step
  functions, a lowered+compiled executable with shardings derived by
  ``core.channels`` (requirement 4), AOT-serialisable so one host compiles
  and every node loads the binary (the analogue of JCSP code-loading
  channels, §4.1).

Load time (lower+compile+serialise) and run time are accounted separately
per requirement 7.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Sequence

import jax

from repro.core import hlo as hlo_mod
from repro.core.channels import Channel, ShardingRules
from repro.core.dsl import ClusterSpec
from repro.core.timing import TimingCollector

# Executable broadcast (JCSP code-loading channel analogue).
from jax.experimental.serialize_executable import (
    deserialize_and_load as _deserialize_and_load,
)
from jax.experimental.serialize_executable import serialize as _serialize

LOAD_PORT = 2000  # paper §6: the load network uses port 2000 ...
LOAD_CHANNEL = 1  # ... and channel number 1 on every node.
APP_PORT = 3000  # application network runs on a different port (§6.1).


# ---------------------------------------------------------------------------
# Deployment plan (HNL / NL analogue).
# ---------------------------------------------------------------------------


@dataclass
class NodePlan:
    node_id: str
    address: str  # ip:port/channel — the only address a node needs
    workers: int
    stage: str = ""  # pipeline stage this node serves ("" pre-pipeline)


@dataclass
class StagePlan:
    """One pipeline stage's slice of the deployment."""

    name: str
    workers: int
    nodes: list[NodePlan] = field(default_factory=list)


@dataclass
class DeploymentPlan:
    """The generated loading/bootstrap schedule of paper §4 / Figure 1."""

    host: str
    nodes: list[NodePlan]
    stages: list[StagePlan] = field(default_factory=list)
    load_port: int = LOAD_PORT
    load_channel: int = LOAD_CHANNEL
    app_port: int = APP_PORT

    @property
    def host_load_address(self) -> str:
        return f"{self.host}:{self.load_port}/{self.load_channel}"

    def load_order(self) -> list[str]:
        """The bootstrap sequence the paper prescribes (§4)."""
        steps = [
            f"HNL: create many-to-one input channel {self.host_load_address}",
            "USER: start one NodeLoader executable per node (identical binary)",
        ]
        for np_ in self.nodes:
            steps.append(
                f"NL[{np_.node_id}]: create input {np_.address}; "
                f"send own IP to {self.host_load_address}"
            )
        steps += [
            f"HNL: received {len(self.nodes)} node IPs; create output channels",
            "HNL: send node-specific NodeProcess to every node "
            "(code-loading channel; single source of class files)",
            "HNL: create HostProcess (Emit + Collect) on the host node",
        ]
        if len(self.stages) > 1:
            chain = " -> ".join(
                f"{sp.name}[{len(sp.nodes)}]" for sp in self.stages
            )
            steps.append(
                f"HNL: route stage results host-side: emit -> {chain} "
                "-> collect (per-stage credit accounting)"
            )
        steps += [
            "ALL: application net channels — input ends created before output "
            "ends; synchronisation messages on the loading network enforce "
            "the order",
            "HP: final barrier; application execution commences",
            "ALL: on termination, nodes return (load_ms, run_ms) to host; "
            "host combines with its own and reports; all resources reclaimed",
        ]
        return steps

    def describe(self) -> str:
        lines = [
            f"DeploymentPlan host={self.host} nodes={len(self.nodes)} "
            f"(load port {self.load_port}, app port {self.app_port})"
        ]
        for np_ in self.nodes:
            stage = f"  stage={np_.stage}" if np_.stage else ""
            lines.append(
                f"  node {np_.node_id}: {np_.address}  "
                f"workers={np_.workers}{stage}"
            )
        lines.append("load order:")
        for i, s in enumerate(self.load_order()):
            lines.append(f"  {i + 1}. {s}")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Compiled SPMD step.
# ---------------------------------------------------------------------------


@dataclass
class StepArtifact:
    """A lowered+compiled SPMD step with analysis accessors."""

    name: str
    fn: Callable
    jitted: Any
    lowered: Any
    compiled: Any
    mesh: Any
    load_ms: float

    def __call__(self, *args, **kw):
        return self.jitted(*args, **kw)

    # -- analysis -----------------------------------------------------------

    def cost(self) -> dict[str, float]:
        """Per-device HLO cost estimates (flops / bytes accessed).

        NOTE: XLA counts ``while``/scan bodies once; use unrolled probe
        programs (launch.roofline) for totals.
        """
        ca = self.compiled.cost_analysis() or {}
        return {
            "flops_per_device": float(ca.get("flops", 0.0)),
            "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        }

    def memory(self):
        return self.compiled.memory_analysis()

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def collectives(self) -> hlo_mod.CollectiveSummary:
        return hlo_mod.parse_collectives(self.hlo_text())

    # -- executable broadcast (code-loading channel analogue) ----------------

    def serialize(self) -> bytes:
        payload, _in_tree, _out_tree = _serialize(self.compiled)
        return payload


# ---------------------------------------------------------------------------
# The builder.
# ---------------------------------------------------------------------------


class ClusterBuilder:
    """Builds deployments from specifications.

    One builder is bound to one mesh (one "cluster"); building the same spec
    with a different builder re-deploys on different hardware with zero user
    changes (paper requirement 4 / §6.1 single-node confidence building).
    """

    def __init__(
        self,
        mesh=None,
        rules: ShardingRules | None = None,
        timing: TimingCollector | None = None,
    ):
        self.mesh = mesh
        self.rules = rules
        self.timing = timing or TimingCollector()

    # -- SPMD step path ------------------------------------------------------

    def build_step(
        self,
        fn: Callable,
        example_args: Sequence[Any],
        *,
        name: str = "step",
        donate_argnums: Sequence[int] = (),
        static_argnums: Sequence[int] = (),
        out_shardings: Any = None,
        compile_now: bool = True,
    ) -> StepArtifact:
        """Lower + compile ``fn`` against ShapeDtypeStruct channels.

        ``example_args`` may be real arrays or ShapeDtypeStructs (dry-run);
        input shardings are carried by the structs (derived via
        ``ShardingRules.struct``), so the user supplies none.
        """
        t0 = time.perf_counter()
        jit_kw: dict[str, Any] = {
            "donate_argnums": tuple(donate_argnums),
            "static_argnums": tuple(static_argnums),
        }
        if out_shardings is not None:
            jit_kw["out_shardings"] = out_shardings
        jitted = jax.jit(fn, **jit_kw)
        with (contextlib.nullcontext() if self.mesh is None
              else jax.set_mesh(self.mesh)):
            lowered = jitted.lower(*example_args)
            compiled = lowered.compile() if compile_now else None
        load_ms = (time.perf_counter() - t0) * 1e3
        self.timing.add("host", "load", load_ms)
        return StepArtifact(
            name=name,
            fn=fn,
            jitted=jitted,
            lowered=lowered,
            compiled=compiled,
            mesh=self.mesh,
            load_ms=load_ms,
        )

    @staticmethod
    def load_serialized_step(payload: bytes, in_tree, out_tree) -> Any:
        """Node-side: load an executable broadcast by the host (§4.1)."""
        return _deserialize_and_load(payload, in_tree, out_tree)

    # -- emit/cluster/collect application path -------------------------------

    def deployment_plan(
        self,
        spec,
        *,
        hosts: Sequence[str] | None = None,
        bind_host: str | None = None,
        launcher: Any = None,
    ) -> DeploymentPlan:
        """Derive the per-stage deployment plan for a spec.

        Node addresses come from the deployment layer when it is known:
        ``hosts=`` (the ssh fan-out shorthand) or a launcher exposing
        ``.hosts`` assigns machines round-robin exactly as the launcher
        will; otherwise ``bind_host`` (every local node-loader dials it).
        With no deployment information at all — a plan derived from the
        spec alone — documentation-placeholder addresses are used, as the
        paper's §4 walkthrough does.
        """
        pipe = spec.as_pipeline() if hasattr(spec, "as_pipeline") else spec
        pipe.validate()
        machines = list(hosts) if hosts else list(
            getattr(launcher, "hosts", None) or []
        )

        def addr_host(i: int) -> str:
            if machines:
                return machines[i % len(machines)]
            if bind_host:
                # Local node-loaders dial the host's bind address; an
                # unroutable wildcard bind resolves to loopback for them.
                return "127.0.0.1" if bind_host == "0.0.0.0" else bind_host
            return f"192.168.1.{100 + i}"  # placeholder: deployment unknown

        nodes: list[NodePlan] = []
        stage_plans: list[StagePlan] = []
        i = 0
        for st in pipe.stages:
            sp = StagePlan(name=st.name, workers=st.workers_per_node)
            for _ in range(st.nclusters):
                np_ = NodePlan(
                    node_id=f"node{i}",
                    address=f"{addr_host(i)}:{LOAD_PORT}/{LOAD_CHANNEL}",
                    workers=st.workers_per_node,
                    stage=st.name if len(pipe.stages) > 1 else "",
                )
                nodes.append(np_)
                sp.nodes.append(np_)
                i += 1
            stage_plans.append(sp)
        return DeploymentPlan(host=pipe.host, nodes=nodes, stages=stage_plans)

    def build_application(self, spec, *, backend: str = "threads",
                          **backend_options):
        """Wire the process network and return a runnable application.

        ``spec`` is a :class:`~repro.core.dsl.ClusterSpec` (the paper's
        emit/cluster/collect shape) or a
        :class:`~repro.core.dsl.PipelineSpec` (one emit, N chained stages,
        one collect); a ClusterSpec is normalised to its one-stage pipeline
        view, so both backends run one code path.

        Backends (all run the *same* spec with zero user-code changes):

        * ``"threads"`` — threads + rendezvous queues in one process
          (``repro.runtime.local``; the paper's §6.1 single-host
          confidence-building mode).  One option:
          ``readonly_delivery=True`` hands work functions read-only
          ndarray views, mirroring the cluster backend's zero-copy
          delivery semantics so in-place mutation bugs surface on one
          host.
        * ``"cluster"`` — real OS processes connected by TCP sockets via the
          Host-Node-Loader / Node-Loader bootstrap of §4 / Figure 1
          (``repro.cluster``).  ``backend_options`` are forwarded to
          :class:`repro.cluster.spawn.ProcessClusterApplication` (e.g.
          ``port=0``, ``slowdown={node_id: seconds_per_item}``).
          *Where* the node-loaders run is pluggable (the deployment
          layer, ``repro.cluster.deploy``): ``launcher=`` takes any
          :class:`~repro.cluster.deploy.base.Launcher` (LocalLauncher
          subprocesses by default, SSHLauncher for real workstations,
          InProcessLauncher threads for tests), and ``hosts=["ws01",...]``
          is shorthand for ssh fan-out over those machines.  The
          registration barrier is policy-driven: ``min_nodes=`` admits a
          degraded start with survivors, ``max_respawns=`` relaunches a
          node that never registers elsewhere, and late joiners are
          shipped LOAD + credits mid-run (``allow_late_join``).
          Robustness knobs: ``max_heals=`` budgets mid-run pool healing
          (a node dying *during* the run is relaunched, warm code
          re-shipped) and ``chaos=`` arms a
          :class:`repro.cluster.chaos.FaultPlan` of injected faults
          (kill/drop/delay/duplicate/corrupt/stall-heartbeat/partition/
          straggler) against the live transport.
          One transport caveat: ndarray payloads cross the wire on a
          zero-copy codec and arrive as *read-only* views — a work
          function that mutates its input in place must ``np.copy`` it
          first (the threads backend hands over the original, writable
          array).
        * ``"service"`` — the same process transport over a *persistent
          warm node pool* (:class:`repro.cluster.service.ClusterService`).
          Pass ``service=`` to run this application as one job of a
          caller-owned pool that stays up (repeat builds of the same spec
          become warm resubmits: no boot, no code shipped); without it an
          ephemeral pool sized from the spec boots for this run and closes
          after.  Remaining ``backend_options`` configure the pool
          (``nodes=``/``workers=`` geometry comes from the spec) —
          including the same ``max_heals=`` / ``chaos=`` robustness
          knobs; the service additionally retries failed jobs when its
          ``submit(..., retries=, backoff=)`` policy is used directly.

        Observability (``"cluster"`` and ``"service"`` backends): pass
        ``trace_path="run.jsonl"`` to append every lifecycle event
        (membership transitions, job submit/done, respawns) as one JSON
        line, and ``http_port=0`` (ephemeral) or a fixed port to serve the
        live status endpoint — ``GET /metrics`` (JSON, or Prometheus text
        with ``?format=prom``), ``/jobs``, ``/nodes``, ``/events?since=N``
        and an auto-refreshing HTML dashboard at ``/``.  See
        :mod:`repro.cluster.telemetry` and ARCHITECTURE.md "Observability".

        Runtimes are imported lazily to keep core dependency-free.
        """
        pipe = spec.as_pipeline() if hasattr(spec, "as_pipeline") else spec
        pipe.validate()
        if backend == "threads":
            readonly = bool(backend_options.pop("readonly_delivery", False))
            if backend_options:
                raise TypeError(
                    f"threads backend takes no options (beyond "
                    f"readonly_delivery), got {sorted(backend_options)}"
                )
            from repro.runtime.local import LocalClusterApplication

            return LocalClusterApplication(
                spec=pipe, plan=self.deployment_plan(pipe),
                timing=self.timing, readonly_delivery=readonly,
            )
        if backend == "cluster":
            from repro.cluster.spawn import ProcessClusterApplication

            # The plan reflects the actual deployment layer: hosts=/launcher
            # machine assignments, or the bind address local loaders dial.
            plan = self.deployment_plan(
                pipe,
                hosts=backend_options.get("hosts"),
                bind_host=backend_options.get("bind_host", "127.0.0.1"),
                launcher=backend_options.get("launcher"),
            )
            return ProcessClusterApplication(
                spec=pipe, plan=plan, timing=self.timing, **backend_options
            )
        if backend == "service":
            from repro.cluster.service import ServiceClusterApplication

            plan = self.deployment_plan(
                pipe,
                hosts=backend_options.get("hosts"),
                bind_host=backend_options.get("bind_host", "127.0.0.1"),
                launcher=backend_options.get("launcher"),
            )
            return ServiceClusterApplication(
                spec=pipe, plan=plan, timing=self.timing, **backend_options
            )
        raise ValueError(
            f"unknown backend {backend!r}; expected 'threads', 'cluster', "
            "or 'service'"
        )
