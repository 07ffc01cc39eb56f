"""How a TPU trace names each kernel. The device op's event name is its
HLO instruction (``%paged_prefill_attention.6 = bf16[...] custom-call(...),
custom_call_target="tpu_custom_call"``): a Pallas call is a custom call
named after the jitted function that wraps it."""

PREFILL_ATTN = "paged_prefill_attention"
DECODE_ATTN = "paged_attention"


def matches(name: str, stats: dict, kernel: str) -> bool:
    """Whether a device op is a call of ``kernel`` (and not of another
    kernel whose name merely contains it)."""
    head = name.split(" = ", 1)[0]
    return (head.startswith(f"%{kernel}.") or head == f"%{kernel}") and \
        "custom-call(" in name
