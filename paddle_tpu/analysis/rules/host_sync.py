"""TPL001 (host-sync in a hot path) and TPL005 (eager
block_until_ready outside bench/profiler code).

A device->host transfer inside compiled or per-step code serializes
the whole pipeline: the host blocks until every queued device
computation retires, then the next step's dispatch starts cold. MPK
measures throughput lost to exactly these, not to FLOPs.
"""
from __future__ import annotations

import ast

from ..context import dotted_name
from ..engine import Rule, Severity, register

# Canonical call targets that force a device->host sync.
_SYNC_CALLS = {
    "jax.device_get": "jax.device_get() blocks until the value is on host",
    "numpy.asarray": "np.asarray() on a device value copies it to host",
    "numpy.array": "np.array() on a device value copies it to host",
}
_SYNC_METHODS = {
    "numpy": ".numpy() materializes the value on host",
    "item": ".item() pulls a scalar to host",
    "tolist": ".tolist() pulls the whole array to host",
}


@register
class HostSyncRule(Rule):
    id = "TPL001"
    name = "host-sync-in-hot-path"
    severity = Severity.ERROR
    rationale = ("device->host transfers inside jitted bodies or the "
                 "serving step loop serialize the device pipeline")

    def check(self, ctx):
        flagged = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            traced = ctx.in_traced_code(node)
            hot = None if traced else ctx.in_hot_function(node)
            if traced is None and hot is None:
                continue
            if hot is not None and self._sanctioned(ctx, hot):
                continue  # the configured async result reader
            where = (f"jitted `{traced.name}`" if traced
                     else f"hot path `{ctx.qualname(hot)}`")
            msg = self._classify(ctx, node, traced is not None)
            if msg:
                flagged.add(id(node))
                yield self.finding(ctx, node, f"{msg} (in {where})")
        # config check (sanctioned_sync): in a hot module the
        # sanctioned async result reader is the ONLY place allowed to
        # call jax.device_get — everywhere else, even outside the
        # configured hot functions, a raw device_get is a second host
        # sync the pipelined pump cannot overlap
        if not ctx.config.sanctioned_sync or \
                not ctx.config.is_hot_module(ctx.path):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or id(node) in flagged:
                continue
            if ctx.resolve(node.func) != "jax.device_get":
                continue
            fn = ctx.enclosing_function(node)
            if fn is not None and self._sanctioned(ctx, fn):
                continue
            qn = ctx.qualname(fn) if fn is not None else "<module>"
            yield self.finding(
                ctx, node,
                "jax.device_get() outside the sanctioned async result "
                f"reader (in `{qn}`; config sanctioned_sync = "
                f"{ctx.config.sanctioned_sync}) — route the transfer "
                "through the one batched reader so the pump loop keeps "
                "a single, overlappable host sync")

    @staticmethod
    def _sanctioned(ctx, fn):
        return ctx.config.is_sanctioned_sync(ctx.qualname(fn))

    def _classify(self, ctx, call, in_traced):
        # method-style syncs: x.numpy() / x.item() / x.tolist()
        if isinstance(call.func, ast.Attribute) and not call.args \
                and not call.keywords:
            hit = _SYNC_METHODS.get(call.func.attr)
            if hit:
                return hit
        target = ctx.resolve(call.func)
        hit = _SYNC_CALLS.get(target)
        if hit:
            return hit
        # float()/int() on a traced value concretize it. Only flagged
        # inside traced code, and not for shape/len() arithmetic, which
        # is static under trace.
        if in_traced and isinstance(call.func, ast.Name) \
                and call.func.id in ("float", "int", "bool") \
                and len(call.args) == 1:
            arg = call.args[0]
            fn = ctx.enclosing_function(call)
            params = ctx.function_params(fn) if fn is not None else set()
            if isinstance(arg, ast.Constant):
                return None
            if ctx.expr_mentions_shape(arg):
                return None
            if ctx.expr_mentions_param(arg, params):
                return (f"{call.func.id}() concretizes a traced value "
                        "(aborts tracing or forces a sync)")
        return None


@register
class EagerBlockRule(Rule):
    id = "TPL005"
    name = "eager-block-until-ready"
    severity = Severity.WARNING
    rationale = ("block_until_ready outside bench/profiler code stalls "
                 "async dispatch; XLA already serializes data dependencies")

    def check(self, ctx):
        if ctx.config.is_bench_path(ctx.path):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "block_until_ready":
                yield self.finding(
                    ctx, node,
                    "block_until_ready() in library code stalls async "
                    "dispatch — only benchmarks/profilers should fence "
                    "the device")
            elif isinstance(node, ast.Call) and \
                    dotted_name(node.func).endswith("block_until_ready"):
                yield self.finding(
                    ctx, node,
                    "jax.block_until_ready() in library code stalls "
                    "async dispatch — only benchmarks/profilers should "
                    "fence the device")
