"""Device milliseconds an admission chunk spends BETWEEN its KDA mixers' two
dense Q40 calls, summed over the chunk's KDA layers. ISSUE 60 defined this
reader as the time of the ``kda.scan`` scopes alone, and the reducer's ops
carry no scope (``harness/reduce_trace.Op`` keeps an instruction's name and
opcode: PERF.md section 7 says which line would have to keep more), so it
reads MORE than its name says: the three convolutions, the q / k norms and
gates, the chunk form (the pair sums in sub-blocks of 16, the forward
substitution, the scan that hands the state on) and the output's norm. On the
chip it read 22.6 ms where the chunk form alone takes 0.93 ms x 20 layers =
18.5 ms (my chip runs, PR 60: ``.bench_scratch/probe60.py``, now
``benchmark/tools/kda_probe.py``). XLA, so a time and no roofline share. The
mean over the admission chunks of the traced window, found by position among
a program run's dense Q40 calls (``harness/ling.block_seconds``). None where
the traced window holds no admission chunk of this model."""

from benchmark.harness import ling

LAYER = "kernels"
UNIT = "ms"
MOVES = "out_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    secs = ling.block_seconds(run.trace, ling.sizes_of(run.cell.config))
    if not secs["chunks"] or secs["chunk_mid"] <= 0:
        return None
    return 1e3 * secs["chunk_mid"] / secs["chunks"]
