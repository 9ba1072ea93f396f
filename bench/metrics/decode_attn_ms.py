"""Device time of the leaf operations under the model's ``attn`` scope
(the attention mixer with its cache write) in each ``jit_decode_step``
execution in the window, per execution (``progtrace``)."""
import progtrace


def read(run):
    return progtrace.decode_scope_ms(progtrace.of(run), "attn")
