"""Toy sizes at which the tests drive each cell on the CPU."""

TOY = {
    "resnet50.train_hbm": (
        dict(rows=32),
        dict(image_size=64, num_classes=10, batch_size=16)),
    "resnet50.train_stream": (
        dict(rows=64, num_workers=2, warmup_boundaries=5),
        dict(image_size=64, num_classes=10, batch_size=16)),
    "openai-gpt.finetune_hbm": (
        dict(rows=8),
        dict(n_layer=2, n_embd=64, n_head=1, n_inner=128, n_positions=256,
             n_ctx=256, vocab_size=100, batch_size=4)),
}
