"""Host staging, inter-pod terms (jaxe/state.py _compile_groups): the self
time of the program's interpod_stage spans, nested in compile_cluster,
per capacity query traced. A program without the span reads nothing."""

from benchmark import spans as S


def read(run):
    queries = len(S.named(run.spans, "bench:query"))
    if not queries or not S.named(run.spans, "interpod_stage"):
        return None
    return S.self_us(run.spans, "interpod_stage") / 1e3 / queries
