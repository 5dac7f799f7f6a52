"""Gap between output tokens, 99th percentile over every gap that ends in
the window (requests from the warm span included). A request still
decoding at the close adds its open gap (close minus its last token)."""


def read(run):
    from chipbench.stats import percentile
    gaps = []
    for rid, stamps in run.rec.stamps.items():
        for a, b in zip(stamps, stamps[1:]):
            if run.in_window(b):
                gaps.append(b - a)
        if len(stamps) < run.reqs[rid].output_len:
            gaps.append(run.t_close - stamps[-1])
    return 1e3 * percentile(gaps, 99) if gaps else None
