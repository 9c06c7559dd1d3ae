"""The served path's profiler spans: one table of names, one way to open one.

A span is a `jax.profiler.TraceAnnotation`: while a profiler trace runs
(`jax.profiler.start_trace`), it lands in that trace's host plane, on the
same clock as the device's ops, so an idle gap on the device can be read
against the host stage that was running.  With the profiler off an
enter/exit costs under a microsecond.  Spans are always on; nothing in the
program reads them.

A span whose name ends in `_wait` is a wait; every other span is work.
"""
from __future__ import annotations

import jax

# name -> (thread that opens it, what it covers)
SPANS = {
    "asap.engine.launch": (
        "admission",
        "ExecutorEngine._launch: pad the batch's tokens into [B, S] and "
        "submit the job"),
    "asap.group.embed": (
        "group worker",
        "the job's tokens to the device and embed_tokens"),
    "asap.group.attn": (
        "group worker",
        "the jitted attention/router step and the blocking fetch of the "
        "routing weights and ids (and of the layer's KV with emit_kv)"),
    "asap.group.dispatch": (
        "group worker",
        "np.asarray(xf), the host argsort, the payload gather and the E "
        "sends"),
    "asap.group.combine_wait": (
        "group worker",
        "combine_recv: waiting on the MoE side"),
    "asap.group.combine": (
        "group worker",
        "concatenation, the H2D copies, the jitted scatter-add and its D2H, "
        "the H2D of y and the residual"),
    "asap.group.final_norm": (
        "group worker",
        "the final norm and the D2H of job.result"),
    "asap.engine.head": (
        "group worker (on_complete)",
        "ExecutorEngine._on_job_done: the last hidden state H2D, lm_head, "
        "argmax and the token D2H"),
    "asap.moe.pack": (
        "MoE worker",
        "joining the taken rows (np.concatenate) and "
        "pack_capacity(_multi)"),
    "asap.moe.launch": (
        "MoE worker",
        "jnp.asarray(xb) and the jitted super-GMM call"),
    "asap.moe.fetch": (
        "MoE worker",
        "np.asarray(yb): the wait for the kernel and its D2H"),
    "asap.moe.unpack": (
        "MoE worker",
        "unpack_capacity(_multi)"),
    "asap.moe.combine_send": (
        "MoE worker",
        "a combine_send to the region's attention group"),
}


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The profiler span `name`, a key of SPANS (`with span(...):`)."""
    return jax.profiler.TraceAnnotation(name)
