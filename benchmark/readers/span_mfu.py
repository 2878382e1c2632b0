"""Model FLOPs over the chip's peak (0..1), over the WHOLE window: for
every span named in ``spans`` that started inside the window, the family's
SPAN_COSTS function ``cost`` gives the FLOPs the CONFIGURATION's
mathematics needs for that one compiled call (or None: a call it cannot
count); their sum over window seconds x the device's bf16 peak x the
cell's chips.  It counts the model and never the implementation — 2 FLOPs
a matmul parameter a REAL token meets, the head at the sampled positions,
attention over the keys a query must see, a state layer's products — so it
reads the same work whatever kernel, layout or fusion does it, and idle
time, padding and copies only lower it.

A span lacks some of what the count needs, and the reader hands the family
the call as the harness knows it (a key the span already carries wins, so a
program that one day writes the count itself is believed):

``rows`` (a decode step)
    the rows the step really decodes: ``decoding`` of the engine turn
    (the span named in ``turn``) that launched it;
``slots`` (a decode step)
    the engine's ``max_batch``: the program's per-row counters also count
    one position for each of the ``slots - rows`` idle or prefilling rows;
``context_tokens`` (a decode step)
    a LOWER bound of the positions the rows' queries see, from the
    histogram ``pages`` (one sample a step: the pages the rows' contexts
    occupy, an idle row one): a row on p pages sees at least
    (p - 1) x page + 1;
``real_tokens`` (a chunk)
    the LEAST number of real tokens the chunk can hold: all of it unless
    it is a prompt's last, then the least that a prompt length of the mix
    (``snap_to``) leaves in a chunk padded to this length, or, where the
    mix has no grid, one token more than a page less.

Nothing counted, or no device trace to be a share of: None."""

from benchmark.lib import peaks


def least_real_tokens(span: dict, prompt_lens, page: int) -> int:
    padded = span["tokens"]
    if not span.get("last"):
        return padded
    fits = [n - span["start"] for n in prompt_lens or ()
            if 0 < n - span["start"] <= padded
            and -(-(n - span["start"]) // page) * page == padded]
    return min(fits) if fits else max(padded - page + 1, 1)


def the_call(span: dict, turn, driver: dict, args: dict) -> dict:
    engine = driver["engine"]
    call = {}
    if "tokens" in span:
        call["real_tokens"] = least_real_tokens(
            span, driver.get("prompt_lens"), engine["page_size"])
    elif turn is not None and "decoding" in turn:
        rows, slots = turn["decoding"], engine["max_batch"]
        call.update(rows=rows, slots=slots)
        pages = driver.get("histograms", {}).get(args.get("pages"))
        if pages and pages["count"]:
            call["context_tokens"] = max(
                0.0, (pages["mean"] - slots) * engine["page_size"] + rows)
    call.update(span)
    return call


def read(args, run):
    cost = getattr(run.cell.family, "SPAN_COSTS", {}).get(args["cost"])
    if cost is None:
        return None
    records = [r for r in run.driver.get("records", [])
               if r.get("kind") == "span"]
    turns = {r["span_id"]: r for r in records if r["name"] == args["turn"]}
    t0, t1 = run.driver["window_wall"]
    flops, counted = 0.0, 0
    for rec in records:
        if rec["name"] in args["spans"] and t0 <= rec["ts"] <= t1:
            work = cost(run.cell.config, the_call(
                rec, turns.get(rec.get("parent_span")), run.driver, args))
            if work is not None:
                flops += work
                counted += 1
    if not counted or run.reduction is None:
        return None
    peak = peaks.peaks_for(run.device_kind)["bf16_flops_per_s"]
    return flops / (run.driver["window_s"] * peak * run.cell.chips)
