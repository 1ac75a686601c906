"""Mean wall milliseconds a window tick spends outside its ``sync`` span,
from the engine's own tick and phase spans (scopes.host_ms_serve).  Moves
serve_tokens_per_s."""
from scopes import READERS

read = READERS["host_ms.serve"]
