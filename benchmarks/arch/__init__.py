"""One module per architecture family. A configuration file names its
module (`"arch": "gpt"` is `benchmarks.arch.gpt`; a dotted name is imported as
it stands), and runners, readers and `rehearse_compile.py` ask that module for
everything that depends on the architecture. The interface is the names in
`harness.ARCH_INTERFACE`, plain functions, no base class:

- `dims(config, rehearse)`: the configuration's published keys as the sizes
  the module's own functions use (`rehearse`: the file's tiny preset). The
  dictionary is the module's own; the harness reads only `vocab_size` from it.
- `program(config, dims)`: `(name in the program's CONFIGS, overrides)`.
- `make_logits(dims)`, `make_loss(dims)`: the plain float32 reference, over
  the program's parameter tree for that model.
- `train_flops_per_token(dims, seq)`, `weight_bytes(dims)`,
  `kv_block_bytes(dims, block_size)`: the operations and bytes the model needs.
- `kernel_costs(dims, batch, seq, chips)`: `{kernel name: {"flops", "bytes"}}`,
  one call of each kernel the train step runs, on one device's shard.

`harness.load_cell` imports the module in the parent process, before the
set-up clock starts, so a module imports JAX only inside its reference."""
