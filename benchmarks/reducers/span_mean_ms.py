"""Mean length (ms) of the harness's deliver spans in the window: ABCI
begin_block's call to commit's return, one per block."""


def read(window, params):
    spans = window.deliver_spans
    if not spans:
        return None
    return sum(b - a for _, a, b in spans) / len(spans) / 1e6
