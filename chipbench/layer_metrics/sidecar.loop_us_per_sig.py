"""Event-loop time of the sidecar per signature that arrived: the item-by-item
Python of parse, flatten + dedup scan and reply (`sidecar.parse_s`,
`service.collect_s`, `sidecar.reply_s` over `sidecar.request_sigs`)."""
from chipbench import spans


def read(src):
    return spans.loop_us_per_sig(
        src, ("sidecar.parse_s", "service.collect_s", "sidecar.reply_s")
    )
