"""Score elements the causal flash kernels compute and the mask then
throws away, as a share of all they compute: 100 x (1 - kept / computed)
over `flash_fwd` and `flash_bwd`, from the program's own always-on record
of the calls it traced (`paddle_tpu.ops.pallas.FLASH_CAUSAL_WORK`,
{kernel: [computed, kept]}). A count, not a time: 33.3 when every running
tile of S=1024 in blocks of 512 is computed whole, 0 if only what the
mask keeps were. None where the program keeps no such record or traced
no causal flash call."""


def read(run):
    from paddle_tpu.ops import pallas as pallas_ops
    work = getattr(pallas_ops, "FLASH_CAUSAL_WORK", None)
    computed = sum(row[0] for row in (work or {}).values())
    if not computed:
        return None
    kept = sum(row[1] for row in work.values())
    return 100.0 * (1.0 - kept / computed)
