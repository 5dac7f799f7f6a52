"""Share of prompt tokens the Balancer gave the partial prefill instance:
sum of ``partial_len`` over sum of input lengths, requests due in the
window that reached the pair. Cronus deployments only."""


def read(run):
    if not run.has_pair:
        return None
    split = [run.reqs[r] for r in run.window_ids
             if run.reqs[r].partial_len > 0]
    if not split:
        return None
    return 100.0 * sum(r.partial_len for r in split) / sum(
        r.input_len for r in split)
