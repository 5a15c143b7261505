"""Builder for ``kind: serve_window`` configurations: a decoder of
sliding-window and full attention layers over grouped K/V heads, with a
per-head output gate, a leading dense layer and softmax-routed experts
beside a shared one (``paddle_tpu.models.window_decoder``) behind
``serving.DecodeEngine`` — the full layers' K/V in the paged block pool,
the window layers' in a ring of pages a sequence — under a closed loop.

The load generator, the fixed schedule (the file's ``order_seed``, the
window opened on ``ramp_tokens``), the frozen heap, the sample (the
clients' first requests, the longest prompt always among them) and the
whole serving sequence are ``builders/serve_hybrid.py``'s :func:`serve`,
run on this builder's engine; the judged LOGITS are ``builders/serve_lm.py``'s
readings, two of them limited (:data:`LIMITS`); the record's keys are
``builders/serve.py``'s,
so the serve readers take it.  What differs: the model and its reference
(``reference/laguna_jnp.py``: ONE full causal pass over prompt + served
tokens on the same bfloat16-rounded weights, the same held experts and
vocabulary slice), and ``weight_bytes``, which counts beside the weights
a step reads the held experts it hit and the window layers' K/V (the
window's mean a step), so that ``kv_bytes_per_token`` is the full
layers' alone."""

from __future__ import annotations

import dataclasses
import json
import types

import numpy as np

from .. import estimators as est, flops_window
from ..decode_book import window_range
from ..harness import say
from ..reference import laguna_jnp
from . import serve as serve_mod, serve_hybrid

#: the limited readings (``serve_lm.LIMITS`` without the token gap and the
#: worst row: at these widths no value separates their sound readings from
#: the precision control's with room, the config's ``reference.why``)
LIMITS = ("logit_rel_l2", "row_rel_l2_median")
#: the per-layer lists of the published config, cut to the depth held
LISTS = ("layer_types", "num_attention_heads_per_layer", "mlp_only_layers",
         "rope_parameters")


def decoder_config(config: dict):
    """The program's config from the file: published keys at the top
    level; the router's width and the held experts from ``deployment``."""
    from paddle_tpu.models.window_decoder import WindowDecoderConfig
    keys = {f.name for f in dataclasses.fields(WindowDecoderConfig)}
    kw = {k: v for k, v in config["model"].items() if k in keys}
    kw.update({k: config[k] for k in LISTS})
    dep = config["deployment"]
    return WindowDecoderConfig(router_experts=dep["router_experts"],
                               held_experts=tuple(dep["held_experts"]), **kw)


def reference_model(config: dict) -> dict:
    """The reference's view of the same file: the published keys and
    lists, with ``num_experts`` the router's width."""
    m = config["model"]
    n = m["num_hidden_layers"]
    return dict(m, num_experts=config["deployment"]["router_experts"],
                layer_types=config["layer_types"][:n],
                num_attention_heads_per_layer=config[
                    "num_attention_heads_per_layer"][:n],
                mlp_only_layers=list(config["mlp_only_layers"]),
                rope_parameters=config["rope_parameters"])


def build_engine(config: dict, seed: int):
    from paddle_tpu.models.window_decoder import WindowDecoder
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config["engine"].items()}
    model = WindowDecoder(decoder_config(config),
                          seed=seed % (2 ** 31 - 1) + 1)
    # started once every client's first request is queued, so that what
    # the worker admits first does not depend on thread timing
    return DecodeEngine(model, DecodeConfig(**kw), auto_start=False)


#: ``serve_hybrid.serve`` — build, warm up, ramp, the window, its traced
#: tail, the sampled requests run to their end, the engine closed — with
#: its ``build_engine`` resolved here: the same code on this engine
serve = types.FunctionType(
    serve_hybrid.serve.__code__,
    dict(serve_hybrid.serve.__globals__, build_engine=build_engine),
    "serve")


def work_end(stamps, t_start: float, t_end: float, tokens: int) -> float:
    """Where the rate's span closes: the sync that brings ``tokens``
    tokens out after the window's first sync, or ``t_end`` if none does
    by then.  The window opens on a point of the fixed schedule
    (``ramp_tokens``) and this closes it on another, so every run times
    the same work.  Closed by the clock instead, the last sync in the
    window falls in a prompt's chunks (~62 tokens a sync) or in a run of
    chains of 8 (~500 tokens a sync, at three times the mean rate) as
    the machine's speed moves it by a tenth of a second, and the rate
    moves 0.7 % with it."""
    times, counts = est.sync_groups(stamps)
    inside = np.flatnonzero((times >= t_start) & (times <= t_end))
    if inside.size < 2:
        return t_end
    out = np.cumsum(counts[inside[1:]])
    k = int(np.searchsorted(out, tokens))
    return float(times[inside[1 + k]]) if k < out.size else t_end


def compare(ref_cfg: dict, m: dict, held, weights, prompt, tokens, served,
            wrong=()) -> dict:
    """One request's readings: the engine's ``served`` logits [n, V] (row t
    is what ``tokens[t]`` was chosen from) against the reference's full
    pass over ``prompt + tokens``, padded with zeros past its end to a
    multiple of ``pad_to`` (causal: the pad changes nothing before it)."""
    plen, n = int(prompt.size), int(tokens.size)
    pad_to = ref_cfg["pad_to"]
    seq = np.zeros(-(-(plen + n) // pad_to) * pad_to, np.int64)
    seq[:plen], seq[plen:plen + n] = prompt, tokens
    want = np.asarray(laguna_jnp.logits(
        weights, seq, m, held=held, layer_prefix=ref_cfg["layer_prefix"],
        wrong=wrong, q_block=ref_cfg["q_block"],
        rows=(plen - 1, plen - 1 + n)))
    got = np.asarray(served, np.float32)
    err = np.linalg.norm(got - want, axis=1)
    norm = np.linalg.norm(want, axis=1)
    sigma = want.std(axis=1)
    gap = (want.max(axis=1) - want[np.arange(n), tokens]) / sigma
    return {"tokens": n, "prompt": plen,
            "logit_rel_l2": float(np.linalg.norm(err) / np.linalg.norm(norm)),
            "row_rel_l2_max": float((err / norm).max()),
            "row_rel_l2_median": float(np.median(err / norm)),
            "first_row_rel_l2": float(err[0] / norm[0]),
            "token_gap_sigma_max": float(gap.max()),
            "reference_sigma_mean": float(sigma.mean())}


def judge(ref_cfg: dict, readings: list) -> dict:
    """The worst reading of each limited quantity over the sampled
    requests, beside its limit."""
    worst = {k: max(r[k] for r in readings) for k in LIMITS} \
        if readings else {}
    return {"worst": worst, "limits": {k: ref_cfg[k] for k in LIMITS},
            "ok": bool(readings) and all(worst[k] <= ref_cfg[k]
                                         for k in LIMITS)}


def run(ctx) -> dict:
    config = ctx.config
    m = reference_model(config)
    ref_cfg = config["reference"]
    s = serve(ctx)
    load, requests = s["load"], s["requests"]
    t_start, t_end = s["t_start"], s["t_end"]
    compiles_in_window = s["compiles_in_window"]

    # -- arithmetic ---------------------------------------------------------
    plen = np.array([r.prompt.size for r in requests])
    stamps = load.stamps[:load.k]
    t_stop = work_end(stamps, t_start, t_end, ctx.traffic["window_tokens"])
    rate = est.sync_rate(stamps, t_start, t_stop)
    naive = est.fixed_window_rate(stamps, t_start, t_end)
    say(f"serve_tokens_per_s: sync to sync {json.dumps(rate)}, closed "
        f"{t_end - t_stop:.3f} s before the window's end; the "
        f"fixed-window count it replaces would read {naive:.3f}")
    submitted = np.flatnonzero((load.t_submit >= t_start)
                               & (load.t_submit < t_end))
    attempted = int(submitted.size)
    failed = int(load.refused[submitted].sum())
    say(f"requests: attempted {attempted}, failed or refused {failed}, "
        f"futures with an exception (any phase) {s['failed_exc']}; "
        f"compilations inside the window: {compiles_in_window}; requests "
        f"the dispatcher had left: {s['requests_left']}")

    # -- correctness --------------------------------------------------------
    held = tuple(config["deployment"]["held_experts"])
    readings = []
    for r, res in zip(s["sample"], s["results"]):
        readings.append(dict(
            compare(ref_cfg, m, held, s["weights"], requests[r].prompt,
                    res.tokens, res.logits), request=int(r)))
        say("reference comparison, request: " + json.dumps(readings[-1]))
    ref = judge(ref_cfg, readings)
    ref["requests"] = [int(r) for r in s["sample"]]
    say("reference comparison: " + json.dumps(ref))
    ok = ref["ok"] and len(s["results"]) == len(s["sample"]) \
        and compiles_in_window == 0 and rate is not None \
        and s["failed_exc"] == 0 and not s["ran_dry"] \
        and not s["stats_end"]["unhealthy"]

    win = serve_mod._delta(s["stats0"], s["stats1"])
    hit = (win.get("moe_experts_hit") or {}).get("chain", 0)
    steps = max(1, win.get("decode_steps", 0))
    dep = config["deployment"]
    events = {"stamps": stamps, "req": load.ev_req[:load.k], "plen": plen,
              "t_start": t_start, "t_end": t_end}
    # the window layers' K/V a decode step reads, the window's mean
    ctx_win = flops_window.decode_contexts(events, *window_range(events))
    window_kv = flops_window.decode_kv_bytes(
        dict(m, layer_types=[t for t in m["layer_types"]
                             if t == flops_window.SLIDING],
             num_hidden_layers=flops_window.layers_of(
                 m, flops_window.SLIDING)), ctx_win)
    return {
        "kind": "serve", "correct": bool(ok), "attempted": attempted,
        "failed": failed, "setup_s": s["setup_s"],
        "end_to_end": {"serve_tokens_per_s": rate["rate"] if rate else 0.0},
        "compiles_in_window": compiles_in_window,
        "engine_stats": win, "engine_stats_whole": s["stats_end"],
        "events": events,
        # what a decode step NEEDS to read beside the full layers' K/V:
        # the weights every step reads, those of the held experts it hit,
        # and the window layers' K/V (the window's means)
        "weight_bytes": flops_window.step_fixed_weight_bytes(
            m, dep["router_experts"])
        + flops_window.expert_weight_bytes(m) * hit / steps
        + window_kv / steps,
        "kv_bytes_per_token": flops_window.layers_of(m, flops_window.FULL)
        * flops_window.kv_bytes_per_position(m),
        "expert_slots_per_step": (dep["held_experts"][1]
                                  - dep["held_experts"][0])
        * (m["num_hidden_layers"] - len(m["mlp_only_layers"])),
        "max_seq_len": config["engine"]["max_seq_len"],
        "trace": s["trace"], "tail": s["tail"], "reference": ref,
        "counts": {"tokens": rate["events"] if rate else 0,
                   "compiles_in_window": compiles_in_window,
                   "attempted": attempted,
                   "logit_rows_compared": sum(r["tokens"]
                                              for r in readings)},
        "memory_samples": s["memory"],
        "hard_exit": True,
    }
