"""Device time of the leaf operations under the model's ``ffn`` scope
(the dense MLP, or the MoE FFN) in each ``jit_decode_step`` execution in
the window, per execution (``progtrace``)."""
import progtrace


def read(run):
    return progtrace.decode_scope_ms(progtrace.of(run), "ffn")
