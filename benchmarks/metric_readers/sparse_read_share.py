"""Over the joined steps that carried no prompt token: the keys the
rows' queries attend to (``llm.pack``'s ``sel_tokens``, from the
adapter's ``ragged_sel_tokens``) over the keys a dense walk would read
(``ctx_tokens`` + the rows' own token each), %.  A program counter: no
device time in it.  A row under ``dense_len`` reads 100%."""
from benchmarks.harness import sala_spans


def read(run):
    found = sala_spans.steps(run, prefill=False)
    if not found or not all("sel_tokens" in pack for pack, _b in found):
        return None
    dense = sum(int(pack["ctx_tokens"]) + int(pack["rows"])
                for pack, _b in found)
    return 100.0 * sum(int(pack["sel_tokens"]) for pack, _b in found) / dense
