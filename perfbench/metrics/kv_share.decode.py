"""Share of the decode program's device seconds spent in ops under
``kv.read`` or ``kv.write``, the paged KV cache's gathers, dequant and
writes (scopes.kv_share_decode).  Moves tpot_p95_s."""
from scopes import READERS

read = READERS["kv_share.decode"]
