"""kern.flash_decode_roofline: the paged exact-attention kernel's share of
its roofline in the traced span — least time for k selected int8 K/V rows
per active slot and kv head (k the configured Top-K), one call per layer and
tick, over the kernel's summed device time, in %. The selection may return
up to k_cap rows and the kernel streams whole blocks, so the share errs low."""

from harness.roofline import flash_decode_need


def read(run):
    share = run.kernel_share("flash_decode", flash_decode_need)
    return None if share is None else 100.0 * share[0]
