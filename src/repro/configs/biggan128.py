"""biggan128: BigGAN at 128x128 (Brock et al., arXiv:1809.11096, Table 1),
at the widths of BigGAN-PyTorch's `scripts/launch_BigGAN_bs256x8.sh`:
G_ch = D_ch = 96, self-attention at 64x64 in G and D, dim_z 120 split
hierarchically, a shared class embedding of 128, 1000 classes, SN_eps
1e-6, BN_eps 1e-5, the hinge loss, D_lr / G_lr = 4. 70.4 M parameters in
G and 88.0 M in D. The model lives in models/biggan.py."""
from repro.models.biggan import BigGANConfig

CONFIG = BigGANConfig(name="biggan128")
