from .optim import adafactor_init, adafactor_update, adamw_init, adamw_update, make_optimizer
from .steps import loss_fn, make_train_step
from .compression import ef_compress, ef_decompress, ef_init
