from perceiver_io_tpu.data.tokenizer import (
    PAD_TOKEN,
    UNK_TOKEN,
    MASK_TOKEN,
    SPECIAL_TOKENS,
    WordPieceTokenizer,
    create_tokenizer,
    train_tokenizer,
    save_tokenizer,
    load_tokenizer,
)
from perceiver_io_tpu.data.pipeline import DataLoader
from perceiver_io_tpu.data.imdb import (
    Collator,
    IMDBDataModule,
    IMDBDataset,
    load_split,
    synthetic_reviews,
)
from perceiver_io_tpu.data.mnist import (
    MNISTDataModule,
    MNISTDataset,
    load_mnist,
    synthetic_digits,
)
from perceiver_io_tpu.data.av import (
    AVDataModule,
    AVDataset,
    load_av_tree,
    synthetic_av_clips,
)
from perceiver_io_tpu.data.imagefolder import (
    ImageFolderDataModule,
    ImageFolderDataset,
    SyntheticImageDataset,
    list_image_folder,
)

__all__ = [
    "PAD_TOKEN",
    "UNK_TOKEN",
    "MASK_TOKEN",
    "SPECIAL_TOKENS",
    "WordPieceTokenizer",
    "create_tokenizer",
    "train_tokenizer",
    "save_tokenizer",
    "load_tokenizer",
    "DataLoader",
    "Collator",
    "IMDBDataModule",
    "IMDBDataset",
    "load_split",
    "synthetic_reviews",
    "MNISTDataModule",
    "MNISTDataset",
    "load_mnist",
    "synthetic_digits",
    "AVDataModule",
    "AVDataset",
    "load_av_tree",
    "synthetic_av_clips",
    "ImageFolderDataModule",
    "ImageFolderDataset",
    "SyntheticImageDataset",
    "list_image_folder",
]
