"""Share of the device's busy seconds spent in ops under the six FQT
quantize scopes ``fqt.q.*`` (scopes.quant_share_train).  Moves
train_tokens_per_s."""
from scopes import READERS

read = READERS["quant_share.train"]
