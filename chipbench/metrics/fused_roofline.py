"""The fused pruned route's share of its HBM roofline: the least bytes a
pruned threshold batch must read (``work/fused_prune.py``) over the
chip's HBM bandwidth, divided by the device time of the fused hit-words
program in the trace. A call's tail blocks are the window's mean per
planner-routed batch of the probe tally ``probe.tail_blocks`` (the
tallies do not split by route); its batch size is the window's mean
serve-flush size. A program without the tallies, or a window in which
the program did not run, reads nothing."""

from bench import trace

PROGRAM = "fused_hit_words"


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if tr is None or peaks is None:
        return None
    lo, hi = tr["window"]
    ns, calls = trace.program_time(tr["events"], PROGRAM, lo, hi)
    b, a = ctx["before"], ctx["after"]
    n0, s0 = b["stages"].get("probe.tail_blocks", (0, 0.0))
    n1, s1 = a["stages"].get("probe.tail_blocks", (0, 0.0))
    flushes = a["flushes"] - b["flushes"]
    if not calls or ns <= 0 or n1 <= n0 or flushes <= 0:
        return None
    gq = (a["batch_total"] - b["batch_total"]) / flushes
    work = ctx["work"]("fused_prune")
    per_call = work.bytes_read(**work.shapes(
        ctx["system"].sharded, gq, (s1 - s0) / (n1 - n0)))
    return 100.0 * calls * per_call / peaks["hbm_bytes_per_s"] / (ns / 1e9)
