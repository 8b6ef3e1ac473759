"""What a `serve_model` cell's rate followed, from its runs' own notes.

    python3 benchmark/serve_stretch_padding.py [--chunk 2048] [--chunk-ms 59.5] run1.out run2.out ...

Each file is the standard output of one untraced ``chipbench.run`` of a
cell whose prompts are prefilled in chunks (``qwen3next-serve-backlog-16k``).
The ``step_ms:`` note lists every tick of the window; a tick that admits a
prompt is a decode step plus the prompt's chunks, so its length counts
them. The balanced stretch is found again as the run of whole ticks whose
count is the stretch's tokens out over the tokens a tick and whose sum is
its seconds; the chunk rows in it that hold no prompt token are padding
(a prompt's last chunk is filled up to the chunk's size). Printed: each
run's rate beside that share, and over the runs the correlation, the fit
and how closely ``1 / (step / lanes + ratio x chunk ms / (chunk x (1 -
padding)))`` gives the rate back. A run with a tick over ``--stall-ms`` is
listed and left out of the fit (a stalled tick reads as chunks). Needs no
chip and no jax. PERF.md, section 7 (d), PR 33, has what it found.
"""
from __future__ import annotations

import argparse
import json
import re
import statistics

import numpy as onp

WINDOW = re.compile(
    r"(\d+) tokens out and (\d+) prompt tokens in over the balanced stretch "
    r"of ([\d.]+) s \(the pool's ratio ([\d.]+); all (\d+) whole ticks, "
    r"[\d.]+ s: (\d+) tokens out")


def read_run(text: str, chunk: int, chunk_ms: float, stall_ms: float):
    """One run's ``(rate, padding share, chunk ms, step ms, ratio, lanes,
    stalled)``, or None where the notes are not there."""
    last = text.strip().splitlines()[-1]
    ticks = re.search(r"step_ms: ([\d. ]+)", text)
    note = WINDOW.search(text)
    if not (last.startswith("{") and ticks and note):
        return None
    rate = json.loads(last)["metrics"]["serve_out_tokens_per_s"]["value"]
    s = onp.array([float(x) for x in ticks.group(1).split()])
    out, taken, sec, ratio, n_ticks, all_out = (
        float(x) for x in note.groups())
    lanes = round(all_out / n_ticks)
    step = float(onp.median(s[s < 1.5 * onp.median(s)]))
    chunks = onp.where(s > step + chunk_ms / 2,
                       onp.round((s - step) / chunk_ms), 0)
    ends = onp.concatenate([[0.0], onp.cumsum(s)]) / 1e3
    found = None
    for k in (int(out) // lanes + d for d in (-1, 0, 1)):
        if 0 < k <= len(s):
            span = ends[k:] - ends[:-k]
            i = int(onp.argmin(onp.abs(span - sec)))
            if found is None or abs(span[i] - sec) < found[0]:
                found = (abs(span[i] - sec), i, k)
    if found is None or found[0] > 0.05:
        return None
    _, i, k = found
    inside = slice(i, i + k)
    n = chunks[inside].sum()
    spent = (s[inside] - step)[chunks[inside] > 0].sum()
    return (rate, 1.0 - taken / (n * chunk), spent / n, step, ratio, lanes,
            bool((s[inside] > stall_ms).any()))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--chunk-ms", type=float, default=59.5)
    ap.add_argument("--stall-ms", type=float, default=1200.0)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    rows = []
    for name in args.runs:
        with open(name) as f:
            got = read_run(f.read(), args.chunk, args.chunk_ms, args.stall_ms)
        if got is None:
            print(f"{name}: no window, ticks or stretch found")
            continue
        rows.append(got)
        print(f"{name}: rate {got[0]:.1f}, padding {100 * got[1]:.2f}% of "
              f"the stretch's chunk rows, {got[2]:.1f} ms a chunk, step "
              f"{got[3]:.2f} ms" + (", a stalled tick" if got[6] else ""))
    a = onp.array([r[:6] for r in rows if not r[6]], float)
    if len(a) < 3:
        return 0
    rate, pad, ms, step, ratio, lanes = a.T
    slope, at0 = onp.polyfit(pad, rate, 1)
    model = 1e3 / (step / lanes + ratio * ms / (args.chunk * (1 - pad)))
    q = statistics.quantiles(rate, n=4)
    rest = rate - slope * (pad - pad.mean())
    qr = statistics.quantiles(rest, n=4)
    print(json.dumps({
        "runs": len(a), "stalled": len(rows) - len(a),
        "corr_rate_padding": float(onp.corrcoef(rate, pad)[0, 1]),
        "fit": [float(at0), float(slope)],
        "corr_rate_model": float(onp.corrcoef(rate, model)[0, 1]),
        "sd_rate": float(rate.std()),
        "sd_rate_less_model": float((rate - model).std()),
        "iqr_over_median": (q[2] - q[0]) / statistics.median(rate),
        "iqr_over_median_padding_taken_out":
            (qr[2] - qr[0]) / statistics.median(rest)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
