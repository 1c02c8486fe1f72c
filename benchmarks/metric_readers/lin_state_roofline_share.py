"""Over the joined steps that carried no prompt token: the least time
the chip's memory could take to read and write each row's lightning
state once a layer (``sala_bytes.lin_state_update_bytes`` of the
published shapes, ``rows`` from ``llm.pack``) over the device time of
the kernel ``lightning_decode``.  Memory bounds it: a decode token does
one multiply-add per float of state."""
from benchmarks.harness import sala_bytes, sala_spans


def read(run):
    return sala_spans.roofline_share(
        run, (sala_spans.DECODE_KERNEL,),
        lambda config, pack, chip: sala_bytes.lin_state_update_bytes(
            config, int(pack["rows"])) / chip["hbm_bytes_per_s"],
        prefill=False)
